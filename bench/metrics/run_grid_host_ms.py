"""Host time of one `run_grid` call until it returns the un-blocked result,
in ms, averaged over the traced sweeps (the harness's `bench.run_grid` span)."""


def read(ctx):
    times = ctx["run_grid_s"]
    return 1e3 * sum(times) / len(times) if times else None
