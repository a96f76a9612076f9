"""Share of 128-row sub-tiles the skip tier decided (provably passing or
failing), over the window's steps, from the step's own tile counters."""


def read(run):
    tiles = run.counts.get("tile_counts")
    if not tiles:
        return None
    total = sum(p + f + a for p, f, a in tiles)
    if not total:
        return None
    return sum(p + f for p, f, _ in tiles) / total * 100.0
