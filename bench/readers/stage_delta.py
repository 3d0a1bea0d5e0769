"""Mean of one or more `gubernator_tpu_stage_duration{stage}` histograms
over the run's traffic, from two `/metrics` scrapes taken outside the window
(a scrape counts live keys by copying the table to the host).

params: stages (summed), per_stage (whose sample count divides). Unit: ms.
"""


def read(ctx, stages, per_stage):
    before, after = ctx.get("stages_before"), ctx.get("stages_after")
    if before is None or after is None:
        return None

    def delta(stage, k):
        return after.get(stage, (0.0, 0.0))[k] - before.get(stage, (0.0, 0.0))[k]

    n = delta(per_stage, 1)
    if n <= 0:
        return None
    return 1e3 * sum(delta(s, 0) for s in stages) / n
