"""Share of the traced window in which a collective ran on a chip and no
other operation did, in percent, averaged over the cell's chips
(``bench/trace.reduce``). Nothing to read where no collective ran."""


def read(ctx):
    if ctx.trace is None or ctx.trace["collective_s"] <= 0:
        return None
    return 100.0 * ctx.trace["exposed_share"]
