"""Device idle time per traced step while the trainer evaluates the
learning rate (the ``train.lr`` span of ``DistributedTrainer.run``), in
ms."""
import trace_layers as TL


def read(ctx):
    return TL.idle_ms(ctx, ("train.lr",))
