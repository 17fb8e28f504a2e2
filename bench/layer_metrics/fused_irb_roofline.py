"""Kernels (`kernels/fused_irb.py`): least time over device time of the
fused inverted-residual kernel. Its events are the HLO custom calls named
`fused_irb_q`; it runs every fusable block (expand -> depthwise -> project,
no squeeze-excitation) once per micro-batch. Work is counted from the
network (`counts.fused_block_work`), at the rows dispatched. A trace whose
event count is not (fusable blocks) x (micro-batches) reads nothing."""
import counts

KERNEL = "fused_irb_q"


def read(run):
    if run.trace is None or not run.traced_batches:
        return None
    per_block = counts.block_ops(run.blocks, run.input_hw)
    fused = [per_block[b.name] for b in run.blocks if counts.fusable(b)]
    events = run.trace.kernel(KERNEL)
    if not fused or len(events) != len(fused) * len(run.traced_batches):
        return None
    least = sum(counts.least_seconds(*counts.fused_block_work(ops, rows), run.peak)
                for rows in run.traced_batches for ops in fused)
    return 100 * least / (sum(e - s for _, s, e in events) * 1e-9)
