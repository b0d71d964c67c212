"""Share of the held experts' roofline that the grouped-matmul kernel
(``kernels/moe_gmm``) reaches in decode, in %: the least time of each
decode step's held experts (its rows' pairs routed to the experts held
here, three d_model x d_expert matmuls each, the weights of the held
experts hit read once, in every layer; ``work_hybrid.routed_experts``,
pairs expected under even routing) summed over the window, over the
device time of the operations named for the gmm kernel."""
from pathlib import Path

from harness import load_module


def read(ctx):
    if not ctx.peak or ctx.summary is None:
        return None
    device_s = ctx.summary.ops_matching("gmm")
    if device_s <= 0:
        return None
    work = load_module(Path(__file__).resolve().parents[1] / "work_hybrid.py")
    layers = ctx.config["num_hidden_layers"]
    least = sum(layers * work.routed_experts(ctx.config, rows)
                .least_s(ctx.peak)
                for rows in ctx.counters["decode_rows"] if rows)
    return least / device_s * 100.0
