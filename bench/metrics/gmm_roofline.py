"""Share of the routed expert FFN's roofline that the grouped-matmul
kernel (``kernels/moe_gmm``) reaches in decode, in %: the least time of
each decode step's routed experts (its tokens x top-k x three
d_model x d_expert matmuls, the weights of the experts hit read once, in
every layer; ``work.routed_experts``) summed over the window, over the
device time of the operations named for the gmm kernel.  A rename of the
kernel leaves this metric silent until the layer has a named scope."""


def read(ctx):
    if not ctx.peak or ctx.summary is None:
        return None
    device_s = ctx.summary.ops_matching("gmm")
    if device_s <= 0:
        return None
    c = ctx.config
    least = sum(c["num_hidden_layers"] * ctx.work.routed_experts(
        rows, d_model=c["hidden_size"], d_expert=c["intermediate_size"],
        topk=c["num_experts_per_tok"], n_experts=c["num_local_experts"],
    ).least_s(ctx.peak) for rows in ctx.counters["decode_rows"] if rows)
    return least / device_s * 100.0
