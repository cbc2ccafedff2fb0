"""Roofline share of the paged decode step: the least time the chip could
take for the window's decode rounds (per round the larger of its FLOPs
over peak and its least bytes over HBM bandwidth, per chip, from shapes in
the cell's architecture module, ``bench/arch/<arch>.py``) over the
device's busy time inside those rounds' calls, from the trace, %."""
from bench import modules


def read(run):
    if run.trace is None or run.peak is None:
        return None
    cfg, t, pk = run.spec.cfg, run.t, run.peak
    arch = modules.arch(cfg)
    least = sum(max(arch.decode_flops(cfg, pos) / t / pk["bf16_flops_per_s"],
                    arch.decode_least_bytes(cfg, pos, t)
                    / pk["hbm_bytes_per_s"])
                for t0, _, _, pos in run.decodes
                if run.in_window(t0) and pos)
    busy = run.trace.busy_s("bench.decode")
    return 100.0 * least / busy if least and busy else None
