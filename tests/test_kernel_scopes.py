"""The five kernel scopes of the device tile programs (ops/kernel_scope.py).

Each jitted tile program, lowered at a small shape on the CPU backend,
must carry all of its scopes, have at least 90% of its HLO instructions
under one, and keep the kernels' symbols through ``strip-debuginfo``:
the persistent compile cache hashes the module after that pass, so a
name that lives only in debug info is lost on a machine whose cache
was warmed without it (the executable comes back unnamed).

The unoptimized HLO names an instruction inside a called computation
relative to that computation (JAX caches the lowering of an inner jit
and calls it from several places); XLA's inliner prefixes the call
site's name. ``scoped_share`` does the same over the call graph, so
it counts what a compiled program will show, on any backend.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from omero_ms_pixel_buffer_tpu.ops import device_deflate as dd
from omero_ms_pixel_buffer_tpu.ops import png
from omero_ms_pixel_buffer_tpu.ops.kernel_scope import SCOPES, kernel

FILTER, HIST, TOKENS, PACK, FRAME = SCOPES

_SCOPE = re.compile(
    r"(?:^|[/(])(ompb_(?:filter|hist|tokens|pack|frame))(?=[/)]|$)"
)
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(
    r"^(?:ROOT )?%?[\w.\-]+ = (?:\(.*?\)|\S+) ([\w\-]+)\("
)
_CALLED = re.compile(
    r"(?:to_apply|body|condition|calls)=%?([\w.\-]+)"
    r"|branch_computations=\{([^}]*)\}"
)
# carry no work of their own: not counted on either side
_PLUMBING = {"parameter", "constant", "tuple", "get-tuple-element"}

H = W = 32
ROWS, ROW_BYTES = H, 1 + W * 2
LANES = 2


def hlo_text(lowered) -> str:
    """The lowered (unoptimized) HLO with each instruction's metadata."""
    from jax._src.lib import xla_client as xc

    options = xc._xla.HloPrintOptions()
    options.print_metadata = True
    return lowered.compiler_ir(dialect="hlo").as_hlo_module().to_string(
        options
    )


def scope_of(op_name: str):
    found = _SCOPE.search(op_name or "")
    return found.group(1) if found else None


def _computations(text: str) -> dict:
    """computation -> its instruction lines, from HLO text."""
    found, name = {}, None
    for raw in text.splitlines():
        line = raw.strip()
        if name is None:
            opened = _COMPUTATION.match(line)
            if opened and " = " not in line.split("{")[0]:
                name = opened.group(1)
                found[name] = []
        elif line == "}":
            name = None
        else:
            found[name].append(line)
    return found


def _called(line: str):
    """The computations an instruction line calls."""
    for single, several in _CALLED.findall(line):
        if single:
            yield single
        else:
            yield from (c.strip().lstrip("%") for c in several.split(","))


def scoped_share(text: str):
    """(share of instructions under a scope, {scope: instructions})
    with call sites' names inherited by what they call."""
    instructions, callers = [], {}  # (computation, own scope); callee -> sites
    for computation, lines in _computations(text).items():
        for line in lines:
            found = _INSTRUCTION.match(line)
            if not found:
                continue
            name = re.search(r'op_name="([^"]*)"', line)
            own = scope_of(name.group(1) if name else "")
            for callee in _called(line):
                callers.setdefault(callee, []).append((computation, own))
            if found.group(1) not in _PLUMBING:
                instructions.append((computation, own))

    memo = {}

    def inherited(comp):
        """The scopes a computation runs under: None in the set means
        some call site has no scope at all."""
        if comp not in memo:
            memo[comp] = {None}  # breaks cycles; the entry stays {None}
            sites = callers.get(comp)
            if sites:
                memo[comp] = set()
                for caller, own in sites:
                    memo[comp] |= {own} if own else inherited(caller)
        return memo[comp]

    counts, named = {}, 0
    for comp, own in instructions:
        scopes = {own} if own else inherited(comp)
        if None not in scopes and scopes:
            named += 1
            for s in scopes:
                counts[s] = counts.get(s, 0) + 1
    return named / len(instructions), counts


def stripped(lowered) -> str:
    """The StableHLO module after `strip-debuginfo`: what the
    persistent compile cache hashes."""
    from jax._src.lib.mlir import ir
    from jax._src.lib.mlir import passmanager

    module = lowered.compiler_ir("stablehlo")
    with module.context:
        copy = ir.Module.parse(str(module))
        passmanager.PassManager.parse(
            "builtin.module(strip-debuginfo)"
        ).run(copy.operation)
        return str(copy)


def _tiles():
    return jnp.zeros((LANES, H, W), jnp.uint16)


def _flat():
    return jnp.zeros((LANES, ROWS * ROW_BYTES), jnp.uint8)


def _tables():
    return dd.build_dynamic_tables(
        np.zeros((LANES, 286), np.int64), np.zeros(LANES, np.int64)
    )


def _rows():
    return jnp.zeros((LANES, H, ROW_BYTES), jnp.uint8)


# program -> (its scopes, how to lower it at the small shape)
PROGRAMS = {
    "_fused_filter_histogram": ({FILTER, TOKENS, HIST}, lambda: (
        dd._fused_filter_histogram.lower(_tiles(), ROWS, ROW_BYTES, 2, "up"))),
    "_fused_filter_histogram_donated": ({FILTER, TOKENS, HIST}, lambda: (
        dd._fused_filter_histogram_donated.lower(
            _tiles(), ROWS, ROW_BYTES, 2, "up"))),
    "_fused_filter_deflate[rle]": ({FILTER, TOKENS, PACK, FRAME}, lambda: (
        dd._fused_filter_deflate.lower(
            _tiles(), ROWS, ROW_BYTES, 2, "up", "rle"))),
    "_fused_filter_deflate_donated[rle]": (
        {FILTER, TOKENS, PACK, FRAME}, lambda: (
            dd._fused_filter_deflate_donated.lower(
                _tiles(), ROWS, ROW_BYTES, 2, "up", "rle"))),
    "_fused_filter_deflate[stored]": ({FILTER, FRAME}, lambda: (
        dd._fused_filter_deflate.lower(
            _tiles(), ROWS, ROW_BYTES, 2, "up", "stored"))),
    "_zlib_dynamic": ({TOKENS, PACK, FRAME}, lambda: (
        dd._zlib_dynamic.lower(_flat(), *_tables()))),
    "_zlib_rle": ({TOKENS, PACK, FRAME}, lambda: (
        dd._zlib_rle.lower(_flat()))),
    "_filtered_to_streams[rle]": ({FILTER, TOKENS, PACK, FRAME}, lambda: (
        dd._filtered_to_streams.lower(_rows(), ROWS, ROW_BYTES, "rle"))),
    "_filtered_to_flat": ({FILTER}, lambda: (
        dd._filtered_to_flat.lower(_rows(), ROWS, ROW_BYTES))),
    "_zlib_stored": ({FRAME}, lambda: dd._zlib_stored.lower(_flat())),
    "filter_batch": ({FILTER}, lambda: (
        png.filter_batch.lower(
            jnp.zeros((LANES, H, W * 2), jnp.uint8), 2, "up"))),
}


@pytest.fixture(scope="module")
def lowered():
    cache = {}

    def get(program):
        if program not in cache:
            cache[program] = PROGRAMS[program][1]()
        return cache[program]

    return get


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_program_carries_all_of_its_scopes_and_no_other(lowered, program):
    _, counts = scoped_share(hlo_text(lowered(program)))
    assert set(counts) == PROGRAMS[program][0]


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_nine_instructions_in_ten_sit_under_a_scope(lowered, program):
    share, counts = scoped_share(hlo_text(lowered(program)))
    assert share >= 0.90, (share, counts)


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_kernel_symbols_survive_strip_debuginfo(lowered, program):
    text = stripped(lowered(program))
    assert "loc(" not in text  # the pass ran
    symbols = set(re.findall(r"func\.func private @(ompb_[a-z]+)", text))
    assert symbols == PROGRAMS[program][0]


def test_the_packers_second_level_is_named_and_has_no_loop(lowered):
    text = hlo_text(lowered("_zlib_rle"))
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for step in ("offsets", "searchsorted", "gather"):
        assert any(
            re.search(rf"ompb_pack/(?:vmap\()?{step}\)?(?:/|$)", n)
            for n in names
        ), step
    # `searchsorted` was a binary search, a `while` of ~log2(tokens)
    # gathers and 40% of the chip's busy time (PERF.md, PR 27); it is a
    # count now, and the packer of neither emit program may grow a loop
    # back (the one loop of an emit program is the token lookup's walk
    # over the payload's chunks, under `ompb_tokens`)
    for program in ("_zlib_rle", "_zlib_dynamic"):
        loops = re.findall(
            r' while\(.*op_name="([^"]*)"', hlo_text(lowered(program))
        )
        assert [scope_of(name) for name in loops] == [TOKENS], loops


# program -> gathers it may hold whose result is as long as the payload
_PAYLOAD_LONG_GATHERS = {
    "_fused_filter_histogram": 0,
    "_fused_filter_histogram_donated": 0,
    "_zlib_dynamic": 0,
    "_zlib_rle": 0,
    "_fused_filter_deflate[rle]": 0,
}


def _loop_bodies(text: str) -> list:
    """The instruction lines of everything a `while` runs, callees
    included."""
    computations = _computations(text)
    todo = [
        callee
        for lines in computations.values()
        for line in lines if " while(" in line
        for callee in _called(line)
    ]
    seen, lines = set(), []
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in computations.get(name, ()):
            lines.append(line)
            todo.extend(_called(line))
    return lines


@pytest.mark.parametrize("program", sorted(_PAYLOAD_LONG_GATHERS))
def test_one_table_lookup_a_position_and_none_in_the_histogram(
    lowered, program
):
    """A gather or a scatter-add costs the chip 5-10 ns an element
    whatever the table, and the two over the 516 token kinds were half
    of its busy time (PERF.md, PR 31). They are contractions with the
    one-hot of the token index now: no program may hold a gather as
    long as the payload or a scatter into the token bins, and a loop
    (the lookup walks the payload in chunks) holds neither."""
    from omero_ms_pixel_buffer_tpu.ops.device_deflate import _TOKEN_KINDS

    text = hlo_text(lowered(program))
    long_gathers = [
        found.group(1)
        for found in re.finditer(
            r"= [a-z]+\d+\[([\d,]*)\]\S* gather\(", text
        )
        if str(ROWS * ROW_BYTES) in found.group(1).split(",")
    ]
    assert len(long_gathers) == _PAYLOAD_LONG_GATHERS[program], long_gathers
    into_the_bins = [
        found.group(1)
        for found in re.finditer(
            r"= [a-z]+\d+\[([\d,]*)\]\S* scatter\(", text
        )
        if str(_TOKEN_KINDS) in found.group(1).split(",")
    ]
    assert not into_the_bins, into_the_bins
    in_a_loop = [
        line for line in _loop_bodies(text)
        if " gather(" in line or " scatter(" in line
    ]
    assert not in_a_loop, in_a_loop


def test_a_named_scope_alone_does_not_reach_the_cache_key():
    """Why `kernel` is an inner jit and not `jax.named_scope` alone:
    after strip-debuginfo the scoped and the unscoped program are the
    same text, so the cache hands back whichever was compiled first."""
    def plain(x):
        return jnp.cumsum(x) * 2

    def scoped(x):
        with jax.named_scope("ompb_tokens"):
            return jnp.cumsum(x) * 2

    x = jnp.arange(8)
    a = stripped(jax.jit(plain).lower(x))
    b = stripped(jax.jit(scoped).lower(x))
    assert a.replace("jit_plain", "jit_f") == b.replace("jit_scoped", "jit_f")
    c = stripped(jax.jit(kernel("ompb_tokens")(plain)).lower(x))
    assert "@ompb_tokens" in c and "@ompb_tokens" not in b


def test_source_lines_do_not_reach_the_cache_key():
    """The key must change with the names, once, and not with every
    edit above a kernel: the stripped text holds no file or line."""
    text = stripped(PROGRAMS["_zlib_rle"][1]())
    assert "device_deflate.py" not in text
    assert "kernel_scope.py" not in text


def test_an_unknown_scope_is_refused():
    with pytest.raises(ValueError):
        kernel("ompb_other")


def test_kernels_keep_their_results_under_vmap_and_outside_jit():
    payload = np.arange(300, dtype=np.uint8) % 7
    bits, nbits = dd._lane_tokens(jnp.asarray(payload))  # outside any jit
    both = jax.vmap(dd._lane_tokens)(jnp.stack([payload, payload]))
    np.testing.assert_array_equal(np.asarray(both[0][1]), np.asarray(bits))
    np.testing.assert_array_equal(np.asarray(both[1][0]), np.asarray(nbits))
    assert dd._lane_tokens.__name__ == TOKENS
