"""Device time of the optimizer per traced step: the operations of the
``optimizer`` scope (the global-norm clip and the AdamW update,
``train/train_step.py``), in ms."""
import trace_layers as TL


def read(ctx):
    return TL.scope_ms(ctx, "optimizer")
