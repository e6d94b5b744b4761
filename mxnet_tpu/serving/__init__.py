"""Production inference serving over deploy artifacts (ROADMAP item 2
— the "millions of users" half of the north star).

The reference framework's deploy story ends at the standalone predict
ABI (``c_predict_api``): load an artifact, call forward, one request
at a time. This package serves it: :class:`InferenceServer` admits
requests through a bounded queue with backpressure and load-shedding,
coalesces them Orca/vLLM-style into a small geometric ladder of bucket
batch shapes (pad to bucket, slice per-request responses back out — so
the XLA program cache stays fixed, no recompile storms under arbitrary
request mixes), dispatches batches to replicas placed across mesh
devices (least-outstanding wins), and wires request latency
percentiles, requests/sec, batch occupancy, queue depth, and
shed/timeout counts into the telemetry JSONL sink as ``serving``
records (``python -m mxnet_tpu.tools.diagnose run.jsonl`` renders the
Serving table).

    pred = mx.deploy.load_compiled("model.mxp")      # bucket ladder
    with serving.InferenceServer(pred, max_queue=256) as srv:
        fut = srv.submit(x)                          # one sample
        y = fut.result(timeout=1.0)

Stateful autoregressive serving (token-by-token decode over a paged
KV cache, streaming, priorities, live weight swap) lives in
:mod:`mxnet_tpu.serving.decode`:

    with serving.DecodeServer(model, params, seq_ladder=[16, 32]) as srv:
        req = srv.submit(prompt_tokens, max_new_tokens=32, priority=2)
        for tok in req.tokens():                     # streams live
            ...

Six models implement the decode-model contract: ``ToyDecoderLM``
(per-head K/V, one position a step), ``latent_moe.LatentMoEDecoderLM``
(a latent cache, routed experts; with ``hc_mult`` > 1 several residual
streams mixed by hyper-connections, and with its next-token module the
SPECULATIVE form of the contract: the module drafts one token, a
two-position verify step accepts it or overwrites it),
``block_diffusion.BlockDiffusionMoEDecoderLM`` (the BLOCK form of the
contract: generation by diffusion over blocks, grouped-query K/V,
softmax-routed experts), ``hybrid_linear_moe.
HybridLinearMoEDecoderLM`` (the STATE form of the contract: delta-rule
linear-attention layers whose state is a fixed array a row, held by the
server beside the pages of a latent-attention layer a group) and
``WindowMoEDecoderLM`` (``window_moe``; the state form again:
sliding-window layers whose last keys and values are a RING a row beside
the pages of the full-attention layers, two counts of gated query heads
over one of key/value heads, softmax-routed experts and a shared one)
and ``SSMHybridDecoderLM`` (``ssm_hybrid``; the state form a third time:
state-space layers whose selective scan keeps ``(states, channels)``
float32 and the last rows of a biased convolution a row, thirteen to
each full-attention layer with ONE key/value head and no position
encoding, a dense MLP, the head tied to the embedding).

Fleet serving — a :class:`Router` fronting N decode replicas with
per-tenant weighted-fair quotas, graceful drain, and transparent
session failover on replica loss (:mod:`mxnet_tpu.serving.router` /
:mod:`mxnet_tpu.serving.fleet`):

    with serving.Router([srv_a, srv_b]) as router:
        req = router.submit(prompt_tokens, tenant="acme")
        for tok in req.tokens():     # survives a replica dying
            ...
"""
from .batcher import BucketLadder, pad_batch, slice_rows
from .server import (InferenceServer, ServerOverloadedError,
                     RequestTimeoutError, ServerClosedError,
                     validate_priority)
from .kvcache import KVCachePool
from .decode import DecodeServer, DecodeRequest, ToyDecoderLM
from .window_moe import WindowMoEDecoderLM
from .ssm_hybrid import SSMHybridDecoderLM
from .fleet import Replica, FleetMonitor
from .router import Router, RouterRequest

__all__ = ["InferenceServer", "BucketLadder", "pad_batch", "slice_rows",
           "ServerOverloadedError", "RequestTimeoutError",
           "ServerClosedError", "validate_priority",
           "KVCachePool", "DecodeServer", "DecodeRequest",
           "ToyDecoderLM", "WindowMoEDecoderLM", "SSMHybridDecoderLM",
           "Router", "RouterRequest",
           "Replica", "FleetMonitor"]
