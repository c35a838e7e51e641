"""Operations the window's steps require (each step's FFN at 1/dp, all
else dense, remat not counted) over the window times the chip's peak."""
import flops as F


def read(ctx):
    c, mix = ctx["counters"], ctx["mix"]
    d = ctx["model"].dims(ctx["conf"])
    ops = sum(F.train_step_flops(d, c["tokens_per_step"], mix["seq_len"], dp)
              for dp, _b, _dt, _tr in c["steps"])
    return 100.0 * ops / (c["window_s"] * ctx["peaks"]["flops_bf16"])
