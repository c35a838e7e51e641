"""The compact FFN kernels' share of their roofline in the traced steps:
the least time the chip needs for the operations and bytes the kept FFN
blocks require (forward and backward), over the kernels' device time."""
import flops as F
import kernels as K


def read(ctx):
    c, mix, pk = ctx["counters"], ctx["mix"], ctx["peaks"]
    d = ctx["model"].dims(ctx["conf"])
    least = 0.0
    for dp, _b, _dt, traced in c["steps"]:
        if traced and dp > 1:
            ops, nbytes = F.train_ffn_kernel_work(d, c["tokens_per_step"], dp)
            least += max(ops / pk["flops_bf16"], nbytes / pk["hbm_bytes_s"])
    spent = K.kernel_seconds(ctx["events"])
    if least == 0.0 or spent == 0.0:
        return None
    return 100.0 * least / spent
