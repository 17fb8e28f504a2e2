"""Kernels (`kernels/depthwise_conv.py`): least time over device time of
the depthwise kernel. Its events are the HLO custom calls named
`depthwise_conv_q`; it runs every depthwise op outside a fusable block once
per micro-batch. Work is counted from the network (`counts.op_work`), at
the rows dispatched. A trace whose event count is not (such ops) x
(micro-batches) reads nothing."""
import counts

KERNEL = "depthwise_conv_q"


def read(run):
    if run.trace is None or not run.traced_batches:
        return None
    dws = [(o, a, c) for b in run.blocks if not counts.fusable(b)
           for o, a, c in counts.block_ops(run.blocks, run.input_hw)[b.name]
           if o.kind == "dw"]
    events = run.trace.kernel(KERNEL)
    if not dws or len(events) != len(dws) * len(run.traced_batches):
        return None
    least = sum(counts.least_seconds(*counts.op_work(o, a, c, rows), run.peak)
                for rows in run.traced_batches for o, a, c in dws)
    return 100 * least / (sum(e - s for _, s, e in events) * 1e-9)
