"""exec_per_config.tune: kernel executions per whole configuration in
the traced window, the reference pass included, counted by the harness's
wrapper around each ``LMStudy.kernels_of`` thunk.  Moves
tune_s_per_config: executions are what selective execution saves."""


def read(ctx):
    configs = ctx.counters.get("configs", 0)
    if not configs:
        return None
    return ctx.counters["kernel_exec"] / configs
