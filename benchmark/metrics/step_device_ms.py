"""step_device_ms: device time of the fused step program (`step_blob`)
per execution, from the profiler trace of the window."""

STEP_PROGRAM = "step_blob"


def read(run):
    if run.device is None:
        return None
    runs, seconds = run.device.runs_matching(STEP_PROGRAM)
    if runs == 0:
        return None
    run.note(f"step_device_ms: {1e3 * seconds / runs:.6f} over {runs} "
             f"executions")
    return 1e3 * seconds / runs
