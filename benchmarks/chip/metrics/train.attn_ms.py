"""Device time of attention per traced step: the operations of the
``attn`` scope (the attention sub-layer and its pre-norm, forward and
backward, ``models/transformer.py``), in ms."""
import trace_layers as TL


def read(ctx):
    return TL.scope_ms(ctx, "attn")
