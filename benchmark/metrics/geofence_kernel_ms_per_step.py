"""geofence_kernel_ms_per_step: device time of the geofence containment
kernel (ops whose name holds `points_in_zones_pallas`) in the traced
window, over the executions of the fused step program (`step_blob`)."""

KERNEL = "points_in_zones_pallas"
STEP_PROGRAM = "step_blob"


def kernel_ms_per_step(run):
    """(kernel ms per step, steps), or None where the trace has no step
    or no kernel op."""
    if run.device is None:
        return None
    steps, _ = run.device.runs_matching(STEP_PROGRAM)
    seconds = run.device.ops_matching(KERNEL)
    if steps == 0 or seconds <= 0.0:
        return None
    return 1e3 * seconds / steps, steps


def read(run):
    found = kernel_ms_per_step(run)
    if found is None:
        return None
    ms, steps = found
    run.note(f"geofence_kernel_ms_per_step: {ms:.6f} over {steps} steps")
    return ms
