"""Device idle time per traced step while the trainer waits for the loss,
reads it back and records the step (the ``train.sync`` and
``train.record`` spans of ``DistributedTrainer.run``), in ms."""
import trace_layers as TL


def read(ctx):
    return TL.idle_ms(ctx, ("train.sync", "train.record"))
