"""Test bootstrap: the suite runs on a virtual 8-device CPU backend, so
the sharding paths are exercised without a chip (a chip run is
`python chip_smoke.py` through the chip tool)."""

import asyncio
import inspect
import os

import pytest

# Force the CPU backend with 8 virtual devices, whatever the ambient
# JAX_PLATFORMS says (env for a fresh import, config for a jax that
# was imported before this file).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Shared zstd gate: the *encode* paths (fixture writing, blosc
# cname="zstd") need the real codec; suites import `needs_zstd` from
# here and skip those cases where python-zstandard isn't installed.
try:
    import zstandard  # noqa: F401

    HAVE_ZSTD = True
except ImportError:
    HAVE_ZSTD = False

needs_zstd = pytest.mark.skipif(
    not HAVE_ZSTD, reason="python-zstandard not installed"
)


# -- minimal async-test support (no pytest-asyncio in the image) -----------


@pytest.fixture
def loop():
    loop = asyncio.new_event_loop()
    yield loop
    loop.run_until_complete(loop.shutdown_asyncgens())
    loop.close()


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    fn = pyfuncitem.obj
    if not inspect.iscoroutinefunction(fn):
        return None
    kwargs = {
        name: pyfuncitem.funcargs[name]
        for name in pyfuncitem._fixtureinfo.argnames
        if name in pyfuncitem.funcargs
    }
    loop = pyfuncitem.funcargs.get("loop")
    if loop is not None:
        loop.run_until_complete(fn(**kwargs))
    else:
        # Leftover-task reaper: ``asyncio.run``'s own teardown
        # cancels leftovers and then waits WITHOUT a bound — a task
        # that survives cancellation (e.g. a cancel swallowed by
        # wait_for's completion race, bpo-42130) wedges the whole
        # suite silently. Reap here with a timeout instead, so a
        # stuck task is a NAMED failure with its stack, not a hung
        # CI job.
        async def _main():
            try:
                await fn(**kwargs)
            finally:
                cur = asyncio.current_task()
                pending = [
                    t for t in asyncio.all_tasks() if t is not cur
                ]
                for t in pending:
                    t.cancel()
                if pending:
                    _done, still = await asyncio.wait(
                        pending, timeout=20
                    )
                    if still:
                        import sys
                        for t in still:
                            print("STUCK TASK:", t, file=sys.stderr)
                            t.print_stack(file=sys.stderr)
                        raise RuntimeError(
                            f"{len(still)} task(s) survived "
                            "cancellation for 20s — see stderr"
                        )

        asyncio.run(_main())
    return True
