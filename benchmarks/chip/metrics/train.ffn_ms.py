"""Device time of the FFN per traced step: the operations of the ``ffn``
scope (``ffn_block`` and its pre-norm, forward and backward, the pattern
kernels among them), in ms."""
import trace_layers as TL


def read(ctx):
    return TL.scope_ms(ctx, "ffn")
