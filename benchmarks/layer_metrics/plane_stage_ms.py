"""Plane cache: host-clock milliseconds to stage one plane into HBM
(read and decode on the host, then the transfer), over every admission
since the process started: `device_plane_stage_seconds` sum ÷
`device_plane_admissions_total` on /metrics. Admissions are set-up's:
they lie before the window's first counter reading, so this is no
delta. None where the program has no such counter."""

HEAD = "device_plane_stage_seconds_sum{"


def read(ctx):
    metrics = ctx["after"]["metrics"]
    admissions = metrics.get("device_plane_admissions_total", 0.0)
    if admissions <= 0:
        return None
    seconds = sum(v for k, v in metrics.items() if k.startswith(HEAD))
    return 1e3 * seconds / admissions
