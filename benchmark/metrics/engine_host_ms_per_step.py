"""engine_host_ms_per_step: the flight recorder's host segments of each
step that began in the window (pack, h2d, dispatch, lane_fetch,
materialize), summed and divided by the number of those steps."""

HOST_STAGES = ("pack", "h2d", "dispatch", "lane_fetch", "materialize")


def read(run):
    total, steps = 0.0, 0
    for rec in run.flight:
        stages = rec.get("stages", {})
        begins = [s["begin_s"] for s in stages.values()]
        if not begins or not run.t_open <= min(begins) <= run.t_close:
            continue
        total += sum(stages[s]["ms"] for s in HOST_STAGES if s in stages)
        steps += 1
    if steps == 0:
        return None
    run.note(f"engine_host_ms_per_step: {total / steps:.3f} over {steps} "
             f"flight records")
    return total / steps
