"""Device time of the head and the loss per traced step: the operations of
the ``head`` scope (the final norm, the tied unembedding, log-softmax and
the loss, forward and backward), in ms."""
import trace_layers as TL


def read(ctx):
    return TL.scope_ms(ctx, "head")
