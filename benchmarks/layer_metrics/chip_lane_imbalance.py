"""Device queue: how unevenly the window's device lanes fell on the
chips: the busiest chip's lanes ÷ the mean over the chips, − 1, in
percent, from the `lanes` of /healthz `cache.device_planes.per_chip`
(the lanes cropped from a chip's planes), after − before. 0 where
every chip served the same count. None where the program reports no
row a chip or no lane ran in the window."""


def _lanes(health: dict) -> dict:
    planes = ((health or {}).get("cache") or {}).get("device_planes") or {}
    rows = planes.get("per_chip")
    if not isinstance(rows, list):
        return {}
    return {row.get("chip"): float(row.get("lanes") or 0) for row in rows}


def read(ctx):
    after = _lanes(ctx["after"]["healthz"])
    before = _lanes(ctx["before"]["healthz"])
    if not after:
        return None
    window = [lanes - before.get(chip, 0.0) for chip, lanes in after.items()]
    mean = sum(window) / len(window)
    if mean <= 0:
        return None
    return 100.0 * (max(window) / mean - 1.0)
