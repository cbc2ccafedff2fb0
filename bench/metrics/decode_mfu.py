"""Share of the chips' bf16 peak that the window's decode rounds reach:
model FLOPs of the live sequences' tokens (the cell's architecture module,
``bench/arch/<arch>.py``, from shapes) over the rounds' host call time
times chips times peak, %."""
from bench import modules


def read(run):
    calls = [(t1 - t0, pos) for t0, t1, _, pos in run.decodes
             if run.in_window(t0) and pos]
    secs = sum(s for s, _ in calls)
    if not secs or run.peak is None:
        return None
    cfg = run.spec.cfg
    arch = modules.arch(cfg)
    work = sum(arch.decode_flops(cfg, pos) for _, pos in calls)
    return 100.0 * work / (secs * run.spec.chips
                           * run.peak["bf16_flops_per_s"])
