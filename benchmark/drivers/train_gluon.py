"""One chip, the path a Gluon user writes: a hybridized network,
``autograd.record`` / ``backward`` / ``gluon.Trainer.step``, which the
framework runs as forward, backward and one fused update dispatch."""
from __future__ import annotations

from . import train_common


class Step:
    def __init__(self, ctx, net):
        import jax
        from mxnet_tpu import gluon
        traffic = ctx.traffic
        net.hybridize()
        self.net = net
        self.trainer = gluon.Trainer(
            net.collect_params(), traffic["optimizer"],
            dict(traffic["optimizer_params"]))
        self.loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        self.placement = jax.devices()[0]

    def __call__(self, x, y, before_update=None):
        from mxnet_tpu import autograd
        with autograd.record():
            loss = self.loss_fn(self.net(x).astype("float32"), y).mean()
        if before_update is not None:
            before_update()
        loss.backward()
        self.trainer.step(1)
        return loss

    def params(self):
        return {name: p.data()._data
                for name, p in self.net.collect_params().items()}

    @staticmethod
    def _counter(name):
        from mxnet_tpu import profiler
        return profiler.counters().get(name, 0)

    def dispatches(self):
        return self._counter("fused_step_dispatches")

    def fallbacks(self):
        return self._counter("fused_step_fallbacks")


def run(ctx):
    return train_common.run(ctx, Step)
