"""Plane cache: the fewest planes resident on any chip of the host at
the window's end: the smallest `planes` of the rows of /healthz
`cache.device_planes.per_chip`. 24 where the Z stack at 32 sections
lies level over four chips; less where a chip was passed over or
evicted. None where the program reports no row a chip (a program
whose cache knows one device, or none)."""


def per_chip(health: dict) -> list:
    """The rows of one /healthz reading, [] where there are none."""
    planes = ((health or {}).get("cache") or {}).get("device_planes") or {}
    rows = planes.get("per_chip")
    return rows if isinstance(rows, list) else []


def read(ctx):
    rows = per_chip(ctx["after"]["healthz"])
    if not rows:
        return None
    return float(min(int(row.get("planes") or 0) for row in rows))
