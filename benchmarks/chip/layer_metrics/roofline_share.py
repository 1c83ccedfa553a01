"""The call's share of the chip's roofline: the least time the chip could take for it (the
larger of the driver's ``flops`` over peak FLOP/s and ``bytes`` over peak HBM bytes/s, a chip)
over the device time it took. The configuration names the bound it expects to bind."""
NAME, UNIT = "roofline_share", "%"
LAYER, MOVES = "Pallas kernels", "call_ms.p50"


def least_seconds(work: dict, peaks: dict, chips: int) -> dict:
    return {"flops": work["flops"] / (peaks["flops_per_s"] * chips),
            "hbm_bytes": work["bytes"] / (peaks["hbm_bytes_per_s"] * chips)}


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    least = least_seconds(run.work, run.peaks, run.chips)
    binds = max(least, key=least.get)
    if binds != run.config["binding_bound"]:
        raise SystemExit(f"{run.config['name']}: {binds} binds, the configuration says {run.config['binding_bound']}")
    return 100.0 * least[binds] / run.trace.device_s_per_call()
