"""The chunk's selective scan against its roofline: the larger of the
least time to move the LIVE lanes' vectors and the request's state and
of the rule's elementwise operations over the chip's published peak,
over the kernel's device time per mixed step. It reads LOW by
construction: the work is ``N E`` exponentials and a handful of
multiply-adds a position on the VPU and the EUP, sequential in time,
and the published peak is the MXU's — 41 M such operations a layer at
512 lanes are 0.2 us there and some hundred times that on the vector
units; the kernel also walks the rung's dead lanes, which the cost
leaves out."""
from benchmark import latent_moe_costs
from benchmark import ssm_hybrid_costs as costs

NAME, UNIT, LAYER = "ssm_chunk_roofline_share", "%", "Kernels"


def compute(ctx):
    s = latent_moe_costs.kernel_s_per_step(ctx, "ssm_chunk_kernel",
                                           "chunk_module")
    lanes = [n for _, n in costs.dispatched(ctx) if n]
    if s is None or ctx.peak is None or not lanes:
        return None
    mean = sum(lanes) / len(lanes)
    sizes = costs.sizes(ctx)
    least = max(
        costs.ssm_chunk_bytes(sizes, mean,
                              ctx.config["bytes_per_value"]["state"])
        / ctx.peak["hbm_bytes_per_s"],
        costs.ssm_chunk_ops(sizes, mean) / ctx.peak["flops_per_s"])
    return 100.0 * least / s
