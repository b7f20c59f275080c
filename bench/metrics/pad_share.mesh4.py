"""Share of the rows a chip holds that are zero-weight padding (%), on
the chip that holds the most padding.  Whole edges are packed onto the
chips and every chip holds as many rows as the fullest one, so each
chip's round runs that many rows and the padding is work thrown away.
From the program's layout counter (``HFLSimulator.shard_rows()``, passed
by the driver as ``chip_rows``)."""


def read(ctx):
    rows = ctx.get("chip_rows")
    if not rows:
        return None
    return max(100.0 * r["pad_rows"] / (r["rows"] + r["pad_rows"])
               for r in rows.values())
