"""Plain reference of `/tile`: a crop of the seeded array."""


def expected(data, request):
    """data: (1, C, Z, Y, X); request: the generator's dict."""
    y, x, h, w = request["y"], request["x"], request["h"], request["w"]
    return data[0, request["c"], request["z"], y : y + h, x : x + w]


def lowered(data, request):
    """The control: the same crop with the low byte of every sample
    dropped (16 -> 8 significant bits), the precision step a faster
    encoder would be tempted by."""
    return expected(data, request) & 0xFF00
