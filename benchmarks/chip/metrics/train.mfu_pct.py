"""Operations the window's steps require, as the configuration's adapter
counts them (``train_step_flops``: each step at its draw's period, remat
not counted), over the window times the chip's peak."""


def read(ctx):
    c, mix, model = ctx["counters"], ctx["mix"], ctx["model"]
    ops = sum(model.train_step_flops(ctx["conf"], c["tokens_per_step"],
                                     mix["seq_len"], dp)
              for dp, _b, _dt, _tr in c["steps"])
    return 100.0 * ops / (c["window_s"] * ctx["peaks"]["flops_bf16"])
