"""chip_ms_per_req: chip time each completed window request cost, in ms:
the serve call's wall seconds on the host's clock, times the cell's chips,
over the requests it completed.  The data plane replays the window with the
chip working back to back (serve_wall_ratio under 1), so this is the chip
time the window's work took, whatever the offered rate."""


def read(ctx):
    done = sum(1 for o in ctx["outcomes"].values()
               if o.completion_s is not None)
    return 1e3 * ctx["wall_s"] * ctx["chips"] / done if done else None
