"""A mesh of chips: ``parallel.DistributedTrainer`` on a ``dp`` mesh over
all the cell's chips, whose step is one compiled mesh program (forward,
backward, gradient exchange, sharded update). Batches arrive sharded over
``dp`` from the framework's own sharded pipeline placement."""
from __future__ import annotations

from . import train_common


class Step:
    def __init__(self, ctx, net):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from mxnet_tpu import gluon
        from mxnet_tpu.parallel import DistributedTrainer, create_mesh
        traffic = ctx.traffic
        self.net = net
        mesh = create_mesh({"dp": ctx.chips})
        self.trainer = DistributedTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), mesh,
            optimizer=traffic["optimizer"],
            optimizer_params=dict(traffic["optimizer_params"]))
        self.placement = NamedSharding(mesh, P("dp"))

    def __call__(self, x, y, before_update=None):
        if before_update is not None:
            self.net(x)             # the parameters take their shapes
            before_update()
        return self.trainer.fit_batch(x, y)

    def params(self):
        self.trainer.sync_gluon_params()
        return {name: p.data()._data
                for name, p in self.net.collect_params().items()}

    def dispatches(self):
        return self.trainer.dispatch_count

    def fallbacks(self):
        return 0


def run(ctx):
    return train_common.run(ctx, Step)
