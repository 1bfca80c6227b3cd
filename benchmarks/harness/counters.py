"""Deltas of the server's own counters between two readings
(`Server.counters()`): what every counter-based layer metric uses."""


def metric_delta(ctx: dict, key: str) -> float:
    """After minus before of one /metrics series (absent = 0)."""
    return (ctx["after"]["metrics"].get(key, 0.0)
            - ctx["before"]["metrics"].get(key, 0.0))


def metric_family_delta(ctx: dict, prefix: str) -> dict:
    """{series: delta} of every /metrics series that starts with prefix."""
    keys = {k for side in ("before", "after")
            for k in ctx[side]["metrics"] if k.startswith(prefix)}
    return {k: metric_delta(ctx, k) for k in sorted(keys)}


def healthz_delta(ctx: dict, *path) -> float:
    """After minus before of one numeric /healthz field (absent = 0)."""
    def at(side):
        node = ctx[side]["healthz"]
        for key in path:
            node = (node or {}).get(key)
        return float(node or 0.0)

    return at("after") - at("before")


def device_served_share(ctx: dict):
    """Percent of the 200 answers between the two readings that were
    encoded on the device (`tile_device_lanes_total`); the rest took
    the program's singleton-batch host path. None without answers."""
    answered = sum(1 for s in ctx["samples"] if s["status"] == 200)
    if not answered:
        return None
    return 100.0 * healthz_delta(ctx, "tile_device_lanes_total") / answered
