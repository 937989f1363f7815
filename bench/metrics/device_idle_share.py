"""Share of the traced window, in %, in which no XLA operation ran on the
device, averaged over the cell's chips (1 - busy / window)."""


def read(ctx):
    red = ctx["trace"]
    busy = [d["busy_s"] for d in red["devices"]]
    if not busy or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / red["window_s"])
