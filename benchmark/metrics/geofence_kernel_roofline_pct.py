"""geofence_kernel_roofline_pct: the least time the chip could take for
one containment of the step's B rows against the Z zones the deployment
holds, over V edges (benchmark/roofline.py, from the configuration's
logical shape), as a share of the kernel's device time per step
(geofence_kernel_ms_per_step). Its note names the bound that sets the
least time."""

from benchmark import loader, roofline

kernel = loader.load_module(loader.METRICS_DIR, "geofence_kernel_ms_per_step")


def read(run):
    import jax

    found = kernel.kernel_ms_per_step(run)
    dep = loader.deployment(run.cfg)
    if found is None or not hasattr(dep, "n_zones"):
        return None
    serve = run.cfg["serve_config"]
    b = int(serve["pipeline.batch_size"])
    z = int(dep.n_zones(run.cfg))
    v = int(serve["pipeline.max_zone_vertices"])
    least, bound = roofline.least_seconds(jax.devices()[0].device_kind,
                                          b, z, v)
    share = 100.0 * least / (found[0] / 1e3)
    run.note(f"geofence_kernel_roofline_pct: {share:.3f} (B={b} Z={z} "
             f"V={v}, {bound}-bound, least {1e3 * least:.6f} ms)")
    return share
