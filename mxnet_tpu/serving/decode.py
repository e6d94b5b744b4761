"""Stateful autoregressive serving: continuous prefill/decode batching
over a paged KV cache.

``InferenceServer`` (PR 9) serves one-shot request/response over
stateless bucket programs; the traffic that matters at million-user
scale is token-by-token decode, where every request carries *state*
(its KV cache) across hundreds of steps. :class:`DecodeServer` is the
Orca/vLLM-style answer composed from machinery this tree already has:

- **Prefill/decode split, fixed program set** — a prompt runs ONE
  prefill pass at its smallest bucketing-ladder rung (program
  ``decode:prefill:s<rung>``), writing its K/V into the paged pool and
  emitting the first token; every subsequent token comes from the ONE
  decode-step program (``decode:step``): a fixed-width batch of
  query-length-1 rows, each layer attending the pool's pages through
  the page table (``kvcache.paged_attention``: the paged Pallas kernel
  reads them where they lie; nothing is gathered) → new-token K/V row
  writes, all inside the compiled program. ``compile_watch.site_stats
  ("decode")`` is the oracle: ``1 + len(ladder)`` programs under ANY
  request mix, zero steady-state recompiles.
- **A prompt rides the decode step in chunks** — on the plain step form
  and on the state form (a model that declares ``chunk_lanes``, below)
  over a layout that can (per-head K and V, packed or not, and the
  latent array: every float layout; beside fixed state a row, the pages'
  own) there is NO prefill program: an admitted request holds its
  pages and its prompt as tokens pending, and each decode step carries
  a chunk of them — ``C`` consecutive positions of ONE request,
  head-most first — on ``C`` more lanes of a MIXED step program
  (``decode:step:chunk:c<C>``, ``_decode_fn_chunk``): the weights are
  streamed once for the rows that decode and the prompt that arrives, no
  row waits for a prompt, and the request's first token is the argmax of
  its last chunk's last lane, fed to the next step where it lies on the
  device. The plain program runs whenever no row has tokens pending.
  A step's budget of prompt tokens is twice the ladder's smallest rung,
  and the mixed program is built at every rung within it (256 and 512 of
  a ladder 256 / 512 / 1024 / 1536; 256 of a ladder of 256): a chunk
  takes the smallest that holds what is pending, the largest while more
  is. The program set is then ``1 +`` those rungs, whatever the ladder.
  The block and speculative forms, an int8 pool and a model that does
  not declare it keep the whole-prompt prefill, ``1 + len(ladder)``.
  ``stats()["chunk_steps"]`` / ``["chunk_tokens"]`` /
  ``["prefill_programs"]`` count it; ``mx:decode.dispatch`` carries
  ``chunk`` and ``chunk_of``.
- **Paged KV cache** (``serving.kvcache``) — fixed-size pages, per
  request page tables, page 0 the masked dump page. Pages allocate on
  demand as generation crosses page boundaries; under pool pressure
  the scheduler preempts the newest lowest-priority active request
  (counted, typed error) rather than stalling everyone.
- **Prefix sharing & multi-model pools** (``MXNET_KV_PREFIX_CACHE``,
  ``pool=``) — a completed prefill registers its page-aligned token
  run in the pool's content-hashed prefix index; a later prompt that
  matches enters decode on the SHARED refcounted pages and feeds only
  the un-cached suffix through the decode step, in chunks (one token a
  step on its own row where the server runs none; greedy
  decode makes the shared stream token-identical to an unshared run —
  the same contract the stepwise-vs-full-forward oracle tests). The
  first write into a still-shared page copies it first (the ``:cow``
  program, over every array the pool carries), and a planned ``kv_cow``
  raise degrades to a private re-prefill, never a wrong token. Several
  servers (several models / weight generations) can ``pool=`` ONE
  process-wide :class:`KVCachePool` under per-model quotas and pool
  priorities, with cross-server preemption when a higher-pool-priority
  tenant starves; the pool's ``step_lock`` serializes their compiled
  steps on the shared arrays.
- **Continuous batching, one step ahead of the host** — one scheduler
  loop interleaves at most one prefill with every decode step, so
  decode steps never starve behind a burst of long prefills, and a
  newly-admitted request starts decoding in the very next step
  alongside requests admitted long ago. The next step is dispatched
  BEFORE the last one's tokens are read back, fed from that step's
  token array where it lies on the device: the launch and everything
  the host does with a step's tokens run under the next step
  (``_tick`` has the order; ``stats()["decode_steps_ahead"]`` and
  ``["decode_drains"]`` say how often it engages).
- **Streaming + cancellation** — ``submit`` returns a
  :class:`DecodeRequest` future whose :meth:`DecodeRequest.tokens`
  iterator yields tokens as steps complete; :meth:`DecodeRequest.
  cancel` (or a passed deadline) frees the request's pages before the
  next decode step, through the counted ``kv_evict`` reclaim path.
- **Priorities** — admission rides the same bounded-queue semantics as
  ``InferenceServer.submit(priority=)``: overload sheds the lowest
  class first (``MXNET_SERVING_PRIORITIES`` classes), and the KV-pool
  preemption picks its victims by the same ordering.
- **Zero-downtime weight hot-swap** — :meth:`DecodeServer.
  swap_weights` loads a new parameter tree (directly, or from a
  topology-neutral checkpoint manifest via
  ``checkpoint.load_param_arrays``) alongside the old one, then flips
  atomically between steps. In-flight requests FINISH on the weights
  they started with (decode batches group by weight version), new
  requests use the new weights from their prefill on, and the old
  tree frees when its last request drains. Same shapes = same
  programs: a swap never recompiles.
- **Faults** — ``serve_admit`` per submit, ``serve_decode`` per decode
  step, ``kv_evict`` per page reclaim: a planned hang at
  ``serve_decode`` deterministically ages streaming requests past
  their deadlines, and the reclaim that follows is counted.
- **Telemetry** — cumulative ``decode`` records (tokens/sec,
  time-to-first-token and inter-token percentiles, KV-pool occupancy/
  evictions, prefill-vs-decode step mix, swaps) flow to the active
  telemetry run, render as the diagnose Decode table, and export as
  ``/metrics`` gauges (``mxnet_tpu.livemetrics``).

The model contract (see :class:`ToyDecoderLM`, the reference
implementation; ``serving.latent_moe.LatentMoEDecoderLM`` is the other
model in the tree). A model DECLARES what it caches and the server's
pool, programs and copy-on-write follow the declaration: the kind of
cache is one layout object in ``serving.kvcache``, picked from the
declaration and the pool's dtype, and the three programs here
(prefill, step, page copy) are written once over whatever arrays that
layout carries:

- ``model.cache_arrays`` — ``((name, trailing shape[, dtype]), ...)``,
  one entry a pool array ``(n_layers, pages, page_size, *trailing)``.
  ``ToyDecoderLM`` declares per-head K and V, ``(("k", (H, D)), ("v",
  (H, D)))`` (a model with ``n_heads``/``head_dim`` and no declaration
  is taken to mean that); a latent-attention model declares ONE array
  whose rows are the compressed K/V and the shared rotary key,
  ``(("kv", (576,), "bfloat16"),)``. A declared dtype is the pool's;
  without one the pool keeps its default (``MXNET_KV_DTYPE``).
- ``model.prefill(params, tokens) -> (logits, *seqs)`` — ``tokens (B,
  L)`` int32, causal; ``logits (B, L, V)``; one ``seq`` a declared
  array, ``(n_layers, B, L, *trailing)`` (K and V: ``(n_layers, B, L,
  H, D)``). Rows at/after the true prompt length may be garbage (the
  server routes their rows to the dump page and never reads their
  logits).
- ``model.decode(params, tokens, positions, attend) -> (logits,
  *new[, counters])`` — ``tokens (B,)``/``positions (B,)`` int32. The
  model never sees the cache: for each layer it asks the server's
  ``attend``. For K and V that is ``attend(layer, q, k_new, v_new,
  scale=None, force_pallas=False)`` — ``q``/``k_new``/``v_new`` ``(B,
  H, D)``, the step's new token — which attends each row's
  ``positions`` earlier keys in the pool plus the new token's own at
  ``positions`` (``kvcache.paged_attention`` bound to the step's
  pools, page tables and positions) and returns ``(B, H, D)``; for a
  latent pool ``attend(layer, q, kv_new, rank=, scale=,
  force_pallas=False)`` (``kvcache.paged_latent_attention``).
  ``logits (B, V)``; one ``new`` a declared array, ``(n_layers, B,
  *trailing)``, which the server writes into the pool after the last
  layer. A model that declares ``chunk_lanes = True`` lets a prompt
  ride the step in chunks: its ``decode(..., head=None, live=None)``
  is handed, by a mixed step, MORE lanes than rows
  (``tokens``/``positions`` ``(B + C,)``; the model is row-wise in
  everything but ``attend``, which the layout splits), ``head (B +
  1,)``, the lanes whose logits are wanted — ``logits`` are theirs
  alone, ``(B + 1, V)``, so that a chunk's lanes do not pay the head —
  and ``live (B + C,)`` bool: a lane that is not live chooses no expert
  (a model without routed experts has nothing to do with it).
- ``model.step_counters`` (optional) — ``(group, (name, ...))``: the
  model's ``decode`` then returns, last, an int32 vector with one
  count a name (what the step's routing did, say). It leaves the
  device with the step's tokens, rides the ``mx:decode.readback`` span
  as arguments (it does not exist before the read-back) and
  accumulates in ``stats()[group]`` (a name that starts with ``max``
  keeps the largest, the others add up).
- ``model.n_layers`` sizes the pool with the declaration.

**The block form of the contract** (``serving.block_diffusion.
BlockDiffusionMoEDecoderLM``): a model that generates by diffusion over
blocks declares ``block_length`` and, in ``decode``'s place,

- ``model.decode_block(params, tokens, positions, attend, live=, head=)
  -> (logits, *new[, counters])`` — ``tokens (B, Q)``, ``Q`` a whole
  number of blocks a row at positions ``positions[b] ..``; ``attend(
  layer, q (B, Q, Hq, D), k_new, v_new (B, Q, Hkv, D), scale=,
  force_pallas=)`` attends what each position may see, over fewer
  key/value than query heads (``kvcache._BlockStep`` over
  ``kvcache.paged_block_attention``); a position that is not ``live (B,
  Q)`` costs no expert; only block ``head[b]`` of a row reaches the
  head: ``logits (B, block_length, V)``; one ``new`` a declared array,
  ``(n_layers, B, Q, *trailing)``;
- ``model.unmask(logits, tokens, masked) -> (tokens, masked)``, the
  model's own unmasking rule by its own settings, and
  ``model.mask_token_id``; ``model.prefill`` runs under the block-causal
  mask.

The server's ONE step program (``_block_decode_fn``) then runs, for
every row, one pass over TWO blocks' positions, ``[p, p + 2Q)`` for a row
whose block starts at ``p``: a row with something masked DENOISES its
block in the first half (the rule unmasks positions in the program;
nothing is written; the second half is dead: it attends nothing, costs
no expert, does not reach the head); a row with nothing masked COMMITS
its block in the first half (its keys and values are written, ``Q`` rows
a row, in each layer before the layer attends) and, in the same pass,
the fresh block at ``p + Q`` — all MASK, known whole before the pass —
has its FIRST DENOISING PASS in the second half, attending the row's
committed keys, the block just committed and its own. So a block of
``Q`` tokens that unmasks one position a pass costs ``Q`` passes, not
``Q + 1``: its commit rides with the next block's first pass. Where the
row ends at or before ``p + Q`` (prompt + ``max_new``, which the
program is told) there is no fresh block and the commit runs by itself —
the degenerate case, which the scheduler never plans (a row's last block
ends the request when it settles, uncommitted) and only a step
dispatched ahead of that read-back runs. The row's block — tokens, what
is masked — lives on the device from step to step and is fed from the
unread step's output, because under a rule that decides on the device
how many positions a pass unmasks the host does not know how a step
ended when it dispatches the next; only the tokens of the block that is
now the row's and two flags a row leave the device (the kind of pass: 1
denoised, 2 committed, 3 committed and denoised the block after). A
block's tokens are pushed together, in order, when the denoising pass
that settled its last position is read back (one step before the pass
that caches them); ``max_new_tokens`` and ``eos_id`` cut the last block.
The page of the block after the one a row works on is held one block
early (a block lies in one page, the next may lie in the next), never a
page past the row's end. The prefill commits the whole blocks of the
prompt and emits no token — it is dispatched and not waited for; the
prompt's remainder opens the first block of the answer as positions
already unmasked. ``stats()["block"]`` sums the passes, one a row a step
whatever it did (``denoise_passes``, a fused pass among them;
``commit_passes``, the commits that ran by themselves;
``fused_commits``; ``tokens_unmasked``; ``blocks_committed``, fused or
not; ``max_passes_a_block``), ``DecodeRequest.unmask_pass`` keeps for
each token the denoising pass of its block that unmasked it (0 the
first: the fused pass is pass 0 of the new block),
``mx:decode.dispatch`` says how many rows the host knows to be
``committing`` / ``denoising`` (``undecided``: the program decides from
the unread step's output) and ``keys_live`` (the committed keys the host
KNOWS the step attends: a lower bound), ``mx:decode.readback`` the
step's ``tokens_unmasked``, ``blocks_committed`` and ``blocks_fused``.
Weight swaps, cancellation, deadlines, priorities and preemption work as
for any model (a block in the making is never cached, so a row ended in
mid-block leaves nothing behind); prefix sharing (its suffix feed is one
token a step), an int8 pool and a latent pool (whose block form is
causal, not all-see-all) are refused with a typed error when the server
is built.

**The speculative form of the contract** (``serving.latent_moe.
LatentMoEDecoderLM`` with its next-token module): a model that drafts
for itself declares ``draft_length`` (1: one draft a step) and, in
``decode``'s place,

- ``model.verify(params, tokens, positions, attend) -> (logits, hidden,
  *new[, counters])`` — the main model over ``tokens (B, Q)``, ``Q =
  draft_length + 1`` consecutive positions from ``positions[b]`` on,
  CAUSAL; ``attend`` is the layout's causal block form
  (``kvcache.paged_latent_causal_attention``); ``hidden (B, Q, D)`` is
  what the drafter reads; one ``new`` a declared array, ``(n_layers, B,
  Q, *trailing)``;
- ``model.draft(params, hidden, tokens, positions, attend) -> (logits,
  *new[, counters])`` — the drafter over the same ``Q`` positions:
  position ``j`` reads ``hidden[:, j]`` and the token AFTER it,
  ``tokens[:, j]``; its cache layers lie behind the main model's
  (``model.cache_layers`` sizes the pool: ``n_layers`` + the drafter's);
- ``model.prefill_draft(params, tokens) -> (logits, hidden, *seqs)`` and
  ``model.draft_prefill(params, hidden, tokens) -> (logits, *seqs)``,
  the same two over a whole prompt.

Verification is greedy, so the served stream is the model's plain
greedy stream token for token, whatever the drafter says. A row holds
its last confirmed token ``x`` at position ``p`` and a draft ``d`` for
``p + 1``. The server's ONE step program (``_spec_decode_fn``) runs, for
every row: the main model over ``[x@p, d@p+1]``, whose argmax ``y1, y2``
are the true tokens at ``p + 1`` and — if ``y1 == d``, the draft
ACCEPTED — at ``p + 2``; then the drafter over ``[(h0, y1)@p, (h1,
y2)@p+1]``, whose argmax at the second position (accepted) or the first
(rejected) is the next draft. Out, a row: ``y1``, ``y2``, accepted, the
next draft, the next position (``p + 1`` or ``p + 2``). Both new rows
are always written, in every layer; nothing is rolled back: the next
step starts at the first unconfirmed position and overwrites. **A row's
position is decided on the device**: tokens, draft and position are fed
from the unread step's output (``prev``/``src``), so the host, which
dispatches a step ahead, keeps pages provisioned for the furthest case
(through ``p + 3`` of the last position it has READ) and learns the
truth a step late; the read-back hands out one or two tokens, and
``max_new_tokens`` / ``eos_id`` cut the second. The prefill program
runs main model and drafter over the prompt, fills both caches and
emits the first token and the first draft. ``stats()["spec"]`` sums
``drafts_verified``, ``drafts_accepted``, ``tokens_out`` and
``positions_run``; ``DecodeRequest.drafts`` keeps for each token the
draft that was verified against it (-1: none — the prefill's token, an
accepted draft's bonus token); ``mx:decode.dispatch`` carries
``keys_live`` (the keys the host KNOWS to be live: a lower bound) and
``undecided`` (rows whose position the program takes from the unread
step), ``mx:decode.readback`` ``accepted`` and ``tokens``. Prefix
sharing (its suffix feed is one token a step), an int8 pool and a
per-head pool (no causal block form) are refused with a typed error
when the server is built.

**The state form of the contract** (``serving.hybrid_linear_moe.
HybridLinearMoEDecoderLM``, ``serving.window_moe.WindowMoEDecoderLM``): a
model whose layers do not all cache a row a token — a linear-attention
layer carries a matrix a head and the last rows of a short convolution
from token to token; a sliding-window layer a RING of its last ``W``
keys and one of its values, key ``t`` in slot ``t % W``, written whole
by a prefill and masked by the row's POSITION, never by content: either
way the same bytes whatever the context — declares, beside
``cache_arrays``,

- ``model.state_arrays = ((name, shape a row, dtype), ...)`` and
  ``model.state_layers``: the server keeps one array a name, ``(state_
  layers, window, *shape)``, behind the pool's pages (``serving.
  kvcache``), donated through the same programs, counted in
  ``stats()["state"]`` (``bytes``, ``rows``, ``rows_live``, ``writes``);
  and ``model.cache_layers``, the layers that DO cache rows in pages,
  which may be fewer than ``n_layers``: the model maps its layer to its
  cache layer (what it asks ``attend`` for) or to its state layer;
- ``model.prefill(params, tokens, lengths) -> (logits, *seqs, *state)``
  — ``lengths (B,)`` the TRUE prompt lengths; one ``state`` a declared
  array, ``(state_layers, B, *shape)``, as it stands after position
  ``lengths - 1``: positions at or past it must leave it untouched;
- ``model.decode(params, tokens, positions, attend, state) -> (logits,
  *new, *state[, counters])`` — ``state`` is the step's
  ``kvcache.RowState``: ``.arrays`` the declared arrays WHOLE, ``.slots
  (B,)`` the row of them each row of the step works on (with its
  ``.inverse``), ``.live (B,)``; the model returns the arrays updated
  (in place, where its kernel aliases them), and a row that is not live
  must leave its slot as it was.

**Which state takes a chunk** is the model's to say, and the server
observes it: a state model that declares ``chunk_lanes = True`` — both
in the tree do: ``WindowMoEDecoderLM`` (a sliding layer's chunk is ``C``
more keys written into slots ``t % W`` of the request's ring and
attended under the band — the ring's validity follows from position
alone) and ``HybridLinearMoEDecoderLM`` (a linear-attention layer's chunk
is the same delta rule over ``C`` more positions FROM THE ROW'S STATE:
``kda_chunk(..., state=)`` on the request's row of ``s``, the
convolution reaching back into its ``conv`` rows, zeros for both where
the chunk starts the prompt, and the row written back at the last live
lane) — is served by the step and the MIXED step
(``_state_decode_fn_chunk``, beside ``_state_decode_fn`` as
``_decode_fn_chunk`` is beside ``_decode_fn``), over pages that can (the
layout beside the state passes on its pages' ``chunks``), with the plain
form's host code — FIFO, one request's chunk a step, the same chunk
sizes — and no prefill program. Its ``decode(..., state, head=None,
live=None, chunk=None)`` is handed ``chunk = (the request's row of the
state arrays, the first lane's position, the live lanes)`` beside
``head`` and ``live``. The chunk's request is NOT a live row of that
step: the rows that decode come first in ``slots``, a row whose prompt
is still pending rides behind ``n_live``, and its state is written by
the chunk's lanes alone (a lane of its own at position 0 would write
slot 0 of its ring, or move its recurrence by a token that is not its
prompt's). A state model that does not declare it (a test's twin of
either) keeps ``_state_prefill_fn``. The dispatch span of a mixed state
step carries ``chunk``, ``chunk_of`` and ``state_rows_live`` (the rows
that decode).

**A row's state belongs to its slot**: a request takes one of the
window's rows (``DecodeRequest.slot``) when it is admitted and keeps it
to its end; its prefill (``_state_prefill_fn``) writes the row WHOLE —
or its chunks do, from position 0 on, masking by position whatever they
have not reached — so a slot's last tenant leaves nothing behind; every step
(``_state_decode_fn``) is handed ``slots``, a permutation of ALL the
window's rows — the live rows' own first, the rest in any order behind
them, not live — so that two weight generations, each with its own step
a pass, never touch each other's rows, and a weight swap leaves rows on
old weights their state. Preemption fails the request like any other
and frees its slot with its pages: whoever sends it again prefills
again, and the state is rebuilt with the pages. A row that needs no
further step (``_ending``) gives its slot to an admission at once — its
last step is already dispatched, and the prefill orders itself behind
it by the arrays, as page writes do. ``mx:decode.dispatch`` and
``mx:decode.readback`` carry ``state_rows_live``, ``mx:decode.prefill``
the ``state_slot`` it writes. The program set stays ``1 +
len(ladder)``, or ``1 +`` the chunk sizes where the state takes a chunk.
Refused with a typed error when the server is built:
prefix sharing (the index holds pages, not the state at a page
boundary, and its suffix feed would have to start from one), the block
and the speculative forms (a pass over several positions a row would
have to keep the state after each until the verdict), an int8 pool.

Sampling is greedy (argmax, in-program): deterministic by
construction, which is what makes "prefill + stepwise cached decode
reproduces the full-sequence forward token-for-token" a testable
contract (``tests/test_decode.py``, on the jnp AND Pallas paths).
"""
from __future__ import annotations

import itertools
import os
import queue as _queue_mod
import resource
import threading
import time
from collections import deque

import numpy as _np

from .. import envs
from ..base import MXNetError
from .. import compile_watch, fault, metering, profiler, telemetry, \
    tracing
from ..bucketing.ladder import BucketLadder
from . import kvcache
from .kvcache import KVCachePool
from .server import (RequestTimeoutError, ServerClosedError,
                     ServerOverloadedError, validate_priority,
                     shed_lowest_locked)

__all__ = ["DecodeServer", "DecodeRequest", "ToyDecoderLM"]

_DONE = object()          # stream sentinel
# what a speculative step says of a row: y1, y2, accepted, the next
# draft, the next position
_SPEC_OUT = 5


class _ParamsVersion:
    """One immutable weight generation: requests pin the version they
    prefilled with; decode batches group by it, so a hot swap never
    mixes generations inside one step."""

    __slots__ = ("version", "tree")

    def __init__(self, version, tree):
        self.version = version
        self.tree = tree


class _Step:
    """One dispatched decode step whose tokens are still on the device:
    the rows it ran (the index is the row's slot), which of them emit a
    token (a mid-suffix feed's output is discarded), the device token
    array, its launch number (``seq``: what its ``decode.dispatch``
    span ``launch`` carries, and the ``decode.readback`` span that waits
    for it), and what ``stats()`` counts when it is read back."""

    __slots__ = ("rows", "emits", "toks", "pages_live", "ahead", "slots",
                 "seq", "launch", "chunk")

    def __init__(self, rows, emits, toks, pages_live, ahead, seq, launch,
                 chunk=None):
        self.rows = rows
        self.emits = emits
        self.toks = toks
        self.pages_live = pages_live
        self.ahead = ahead
        self.seq = seq
        self.launch = launch
        # a mixed step's chunk: ``(the row it fed, its tokens, the
        # program's lanes)``
        self.chunk = chunk
        # {id(row): its slot} in this step's output
        self.slots = {id(r): i for i, r in enumerate(rows)}


class DecodeRequest:
    """One streaming generation: a future over the full token list
    plus a per-token stream. The server appends each generated token
    to the bounded stream queue the moment its step completes;
    :meth:`tokens` iterates them live, :meth:`result` blocks for the
    whole list. ``request_id`` joins log lines, shed/timeout errors,
    and telemetry."""

    __slots__ = ("prompt", "max_new", "priority", "deadline", "eos_id",
                 "request_id", "t_submit", "pages", "generated",
                 "params", "state", "_cancelled", "_stream", "_event",
                 "_error", "_last_emit", "trace_args",
                 "_t_trace", "pending", "pending_pos", "prefix_cached",
                 "unread", "blk_start", "blk_x", "blk_masked",
                 "blk_when", "blk_pass", "unmask_pass", "draft",
                 "drafts", "slot")

    def __init__(self, prompt, max_new, priority, deadline, eos_id,
                 request_id):
        self.prompt = prompt
        self.max_new = max_new
        self.priority = priority
        self.deadline = deadline
        self.eos_id = eos_id
        self.request_id = request_id
        self.t_submit = tracing.now()
        self.pages = []
        self.generated = []
        self.params = None            # _ParamsVersion, set at prefill
        self.state = "queued"         # queued|active|done|failed
        self._cancelled = False
        # bounded by construction: at most max_new tokens + sentinel
        self._stream = _queue_mod.Queue(maxsize=max_new + 2)
        self._event = threading.Event()
        self._error = None
        self._last_emit = None    # last token's stamp; None before the first
        self.trace_args = None    # span args while traced (carries an
                                  # adopted router request_id, if any)
        self._t_trace = None      # where the open ring phase began
        # prefix-cache suffix feed: tokens still to run through the
        # decode-step program (their outputs are discarded until the
        # last one, which IS the first generated token), and the
        # absolute position the next one writes at
        self.pending = None
        self.pending_pos = 0
        self.prefix_cached = 0    # prompt tokens served from the index
        # tokens of this request that a dispatched decode step has
        # computed and the scheduler has not read back yet: what it
        # plans from is ``len(generated) + unread``
        self.unread = 0
        # a block model's row (``DecodeServer``'s docstring), as of the
        # last step READ BACK: where the block it is working on starts,
        # the block's tokens, which of them are still masked, the pass
        # in which each was unmasked (-1: a prompt token) and the
        # denoising passes the block has had; ``unmask_pass[i]`` is the
        # pass of its block (0 the first) that unmasked ``generated[i]``
        self.blk_start = 0
        self.blk_x = self.blk_masked = self.blk_when = None
        self.blk_pass = 0
        self.unmask_pass = []
        # a speculative model's row: the draft for the position after
        # the last token READ BACK, and for each generated token the
        # draft that was verified against it (-1: none)
        self.draft = 0
        self.drafts = []
        # a state model's row: the row of the server's state arrays it
        # holds from its admission to its end (None: none)
        self.slot = None

    def done(self):
        return self._event.is_set()

    def cancel(self):
        """Ask the server to drop this request: it is reaped before
        the next decode step and its KV pages are freed then (the
        ``kv_evict`` path). A cancelled request completes WITHOUT an
        error — the stream just ends, :meth:`result` returns the
        tokens generated so far, and ``state == "cancelled"`` tells
        the story. Safe from any thread; idempotent."""
        self._cancelled = True

    def result(self, timeout=None):
        """Block for the full generation; returns an int32 array of
        the generated tokens (the partial list, for a cancelled
        request). Raises the request's error (timeout, shed,
        preemption, the model's own)."""
        if not self._event.wait(timeout):
            raise RequestTimeoutError(
                "request %s did not complete within %ss"
                % (self.request_id, timeout))
        if self._error is not None:
            raise self._error
        return _np.asarray(self.generated, _np.int32)

    def tokens(self, timeout=None):
        """Iterate generated tokens as they stream in. ``timeout``
        bounds the wait per token. Ends when generation completes;
        raises the request's error (after yielding every token that
        landed before it)."""
        while True:
            item = self._stream.get(timeout=timeout)
            if item is _DONE:
                if self._error is not None:
                    raise self._error
                return
            yield item

    # -- server side -------------------------------------------------------
    def _push(self, token):
        try:
            self._stream.put_nowait(int(token))
        except _queue_mod.Full:       # unreachable by construction
            pass

    def _complete(self, error=None, state=None):
        """Finalize: the state is set BEFORE the event fires, so a
        woken waiter can never observe a stale one. First caller wins
        — a ``stop()`` racing the scheduler (or a degraded stop whose
        wedged scheduler later retires the same request) must not
        overwrite the terminal state. The ``_DONE`` sentinel ALWAYS
        lands: on a full stream (unreachable by construction, but the
        failure mode is a consumer hung forever on the bounded queue)
        the oldest unconsumed token is dropped to make room — losing
        a buffered token to deliver the terminal error beats hanging
        ``tokens()``."""
        if self._event.is_set():
            return
        self._error = error
        self.state = state if state is not None \
            else ("failed" if error is not None else "done")
        while True:
            try:
                self._stream.put_nowait(_DONE)
                break
            except _queue_mod.Full:
                try:
                    self._stream.get_nowait()
                except _queue_mod.Empty:
                    pass
        self._event.set()


# ---------------------------------------------------------------------------
# the reference decode model
# ---------------------------------------------------------------------------

class ToyDecoderLM:
    """A minimal pre-LN transformer LM implementing the decode-model
    contract — the reference the server's tests, example and benchmark
    (``benchmark/configs/opt-6.7b.json``) drive. Prefill attention is
    ``flash_attention(causal=True)``; decode attention is whatever the
    server's ``attend`` does over its pool; ``use_pallas`` forces the
    Pallas kernels in interpret mode off-TPU so both kernel paths are
    testable on CPU.
    Parameters are a FLAT ``{name: array}`` dict, so a checkpoint
    manifest round-trips them by name (the hot-swap recipe)."""

    # ``decode`` takes ``head`` and ``live``: a prompt may ride the step
    # in chunks
    chunk_lanes = True

    def __init__(self, vocab=32, n_layers=2, n_heads=2, head_dim=8,
                 d_ff=None, max_len=256, use_pallas=False):
        self.vocab = int(vocab)
        self.n_layers = int(n_layers)
        self.n_heads = int(n_heads)
        self.head_dim = int(head_dim)
        self.d_model = self.n_heads * self.head_dim
        self.d_ff = int(d_ff) if d_ff else 4 * self.d_model
        self.max_len = int(max_len)
        self.use_pallas = bool(use_pallas)
        self._scale = 1.0 / float(self.head_dim) ** 0.5
        # what the server's pool holds for this model: per-head K and V
        self.cache_arrays = (("k", (self.n_heads, self.head_dim)),
                             ("v", (self.n_heads, self.head_dim)))

    def init_params(self, seed=0):
        import jax
        import jax.numpy as jnp
        keys = iter(jax.random.split(jax.random.PRNGKey(seed), 128))

        def _w(shape, s=0.1):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * s)

        D, F, V = self.d_model, self.d_ff, self.vocab
        p = {"embed": _w((V, D), 0.5), "pos": _w((self.max_len, D), 0.1),
             "out_g": jnp.ones((D,)), "out_b": jnp.zeros((D,)),
             "wout": _w((D, V), 0.2)}
        for i in range(self.n_layers):
            p.update({
                "l%d.att_g" % i: jnp.ones((D,)),
                "l%d.att_b" % i: jnp.zeros((D,)),
                "l%d.wq" % i: _w((D, D)), "l%d.wk" % i: _w((D, D)),
                "l%d.wv" % i: _w((D, D)), "l%d.wo" % i: _w((D, D)),
                "l%d.ffn_g" % i: jnp.ones((D,)),
                "l%d.ffn_b" % i: jnp.zeros((D,)),
                "l%d.w1" % i: _w((D, F)), "l%d.w2" % i: _w((F, D)),
            })
        return p

    @staticmethod
    def _ln(x, g, b):
        import jax.numpy as jnp
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5) * g + b

    def prefill(self, params, tokens):
        import jax
        import jax.numpy as jnp
        from ..parallel.flash_attention import flash_attention
        B, L = tokens.shape
        H, Dh = self.n_heads, self.head_dim
        h = params["embed"][tokens] + params["pos"][:L][None]
        ks, vs = [], []
        for i in range(self.n_layers):
            x = self._ln(h, params["l%d.att_g" % i],
                         params["l%d.att_b" % i])
            q = (x @ params["l%d.wq" % i]).reshape(B, L, H, Dh)
            k = (x @ params["l%d.wk" % i]).reshape(B, L, H, Dh)
            v = (x @ params["l%d.wv" % i]).reshape(B, L, H, Dh)
            a = flash_attention(q, k, v, causal=True,
                                scale=self._scale,
                                force_pallas=self.use_pallas)
            h = h + a.reshape(B, L, -1) @ params["l%d.wo" % i]
            x = self._ln(h, params["l%d.ffn_g" % i],
                         params["l%d.ffn_b" % i])
            h = h + jax.nn.relu(x @ params["l%d.w1" % i]) \
                @ params["l%d.w2" % i]
            ks.append(k)
            vs.append(v)
        logits = self._ln(h, params["out_g"], params["out_b"]) \
            @ params["wout"]
        return logits, jnp.stack(ks), jnp.stack(vs)

    def decode(self, params, tokens, positions, attend, head=None,
               live=None):
        import jax
        import jax.numpy as jnp
        B = tokens.shape[0]
        H, Dh = self.n_heads, self.head_dim
        h = params["embed"][tokens] + params["pos"][positions]
        k_new, v_new = [], []
        for i in range(self.n_layers):
            x = self._ln(h, params["l%d.att_g" % i],
                         params["l%d.att_b" % i])
            q = (x @ params["l%d.wq" % i]).reshape(B, H, Dh)
            k = (x @ params["l%d.wk" % i]).reshape(B, H, Dh)
            v = (x @ params["l%d.wv" % i]).reshape(B, H, Dh)
            # the cache attends: the row's earlier keys from the pool
            # plus this token's own K/V at its position
            a = attend(i, q, k, v, scale=self._scale,
                       force_pallas=self.use_pallas)
            h = h + a.reshape(B, -1) @ params["l%d.wo" % i]
            x = self._ln(h, params["l%d.ffn_g" % i],
                         params["l%d.ffn_b" % i])
            h = h + jax.nn.relu(x @ params["l%d.w1" % i]) \
                @ params["l%d.w2" % i]
            k_new.append(k)
            v_new.append(v)
        if head is not None:
            # a mixed step's chunk lanes do not pay the head: only the
            # lanes whose logits are read reach it
            h = h[head]
        logits = self._ln(h, params["out_g"], params["out_b"]) \
            @ params["wout"]
        return logits, jnp.stack(k_new), jnp.stack(v_new)


# ---------------------------------------------------------------------------
# the server
# ---------------------------------------------------------------------------

class DecodeServer:
    """Continuous-batching autoregressive server (module docstring has
    the architecture). ``seq_ladder`` buckets PROMPT lengths (ints, a
    :class:`BucketLadder`, or None for a geometric [16..128] default);
    rungs are page-aligned via ``BucketLadder.aligned``, and when the
    model declares a ``max_len`` the ladder top + ``max_new_tokens``
    must fit it (a silently clamped positional gather would emit
    wrong tokens with no error). ``window`` is the decode step's fixed
    batch width (``MXNET_DECODE_WINDOW``); ``max_new_tokens`` caps any
    request's generation budget and, with the top rung, sizes the page
    tables. ``start=False`` leaves the scheduler unstarted so tests
    drive :meth:`_tick` deterministically."""

    def __init__(self, model, params, *, seq_ladder=None,
                 max_new_tokens=64, window=None, page_size=None,
                 pool_pages=None, pool=None, pool_quota=None,
                 pool_priority=0, prefix_cache=None, share_group=None,
                 max_queue=64, default_deadline_ms=None,
                 record_every=None, name=None, device=None,
                 mesh=None, start=True):
        import jax
        from .. import compile_watch
        # over a mesh (given, or the one the model is bound to) the model
        # says how a layer is shared (``sharded_over``: the whole model,
        # bound; ``local()``: what one chip runs, under ``shard_map``)
        mesh = mesh if mesh is not None else getattr(model, "mesh", None)
        if mesh is not None:
            if not hasattr(model, "sharded_over"):
                raise MXNetError(
                    "DecodeServer: mesh= needs a model that says how its "
                    "layers are shared by the chips (sharded_over, local, "
                    "param_specs: serving.window_moe) — %s does not"
                    % type(model).__name__)
            model = model.sharded_over(mesh)
        self._mesh = mesh
        self._shards = int(model.shards) if mesh is not None else 1
        self._block = int(getattr(model, "block_length", 0) or 0)
        self._spec = int(getattr(model, "draft_length", 0) or 0)
        for attr in ("prefill", "n_layers") + (
                ("decode_block", "unmask", "mask_token_id") if self._block
                else ("verify", "draft", "prefill_draft", "draft_prefill")
                if self._spec else ("decode",)):
            if not hasattr(model, attr):
                raise MXNetError(
                    "DecodeServer: model lacks %r — the decode-model "
                    "contract is prefill/decode, n_layers and the "
                    "declaration of what it caches, cache_arrays = "
                    "((name, trailing shape[, dtype]), ...) (see "
                    "serving.decode.ToyDecoderLM, which declares "
                    "per-head K and V, and serving.latent_moe, which "
                    "declares one latent array; a model with a "
                    "block_length has decode_block, unmask and "
                    "mask_token_id in decode's place: "
                    "serving.block_diffusion; a model with a "
                    "draft_length has verify, draft, prefill_draft and "
                    "draft_prefill: serving.latent_moe)" % attr)
        specs, cache_dtype = kvcache.declared_arrays(model)
        state, state_layers = kvcache.declared_state(model)
        self._state = bool(state)
        # a model that drafts for itself caches its drafter's layers
        # behind its own
        n_layers = int(getattr(model, "cache_layers", model.n_layers))
        self._counters = getattr(model, "step_counters", None)
        # ``_model`` is the whole model, the host's; ``_chip_model`` what
        # a program traces: the same object, or under a mesh one chip's
        self._model = model
        self._chip_model = model.local() if mesh is not None else model
        self.name = name
        self._device = device if device is not None else jax.devices()[0]
        self._window = max(1, int(window) if window is not None
                           else envs.get_int("MXNET_DECODE_WINDOW"))

        if seq_ladder is None:
            seq_ladder = BucketLadder.geometric(128, 16)
        elif not isinstance(seq_ladder, BucketLadder):
            seq_ladder = BucketLadder(seq_ladder)
        self._max_new = int(max_new_tokens)
        if self._max_new < 1:
            raise MXNetError("DecodeServer: max_new_tokens must be "
                             ">= 1, got %d" % max_new_tokens)
        if pool is not None:
            if pool_pages is not None:
                raise MXNetError(
                    "DecodeServer: pool_pages= conflicts with an "
                    "external pool= — size the shared pool once, "
                    "where it is built")
            if page_size is not None \
                    and int(page_size) != pool.page_size:
                raise MXNetError(
                    "DecodeServer: page_size=%d does not match the "
                    "shared pool's %d" % (int(page_size),
                                          pool.page_size))
            if (pool.n_layers, pool.array_specs, pool.state_specs) \
                    != (n_layers, specs, state):
                raise MXNetError(
                    "DecodeServer: shared pool geometry (layers=%d, "
                    "arrays=%s) does not match the model's (%d, %s) — "
                    "co-tenant models must agree on the page shape"
                    % (pool.n_layers, pool.array_specs, n_layers, specs))
            if pool.shards != self._shards:
                raise MXNetError(
                    "DecodeServer: the shared pool lies on %d chips, the "
                    "server's mesh has %d" % (pool.shards, self._shards))
            self._pool = pool
        else:
            self._pool = KVCachePool(n_layers, arrays=specs,
                                     page_size=page_size,
                                     n_pages=pool_pages,
                                     dtype=cache_dtype,
                                     device=self._device, state=state,
                                     state_layers=state_layers,
                                     state_rows=self._window,
                                     shardings=self._pool_shardings
                                     if mesh is not None else None)
        self._owner = self._pool.attach(
            name or "model", quota=pool_quota, priority=pool_priority,
            preempt=self._pool_preempt_cb)
        self._prefix_on = bool(prefix_cache) \
            if prefix_cache is not None \
            else envs.get_bool("MXNET_KV_PREFIX_CACHE")
        self._share_group = share_group
        self._preempt_asks = 0    # co-tenant give-back requests pending
        if self._state:
            self._check_state_model()
        if self._block:
            self._check_block_model()
        if self._spec:
            self._check_spec_model()
        if mesh is not None and not (
                self._state and self._pool.layout.chunks
                and getattr(model, "chunk_lanes", False)):
            raise MXNetError(
                "DecodeServer: over a mesh the state form whose prompts "
                "ride the step in chunks is written (the step, the mixed "
                "step and the page copy under shard_map) — the plain, "
                "block and speculative forms and a whole-prompt prefill "
                "are not, yet")
        # prompt rungs fill whole pages; the table width covers the
        # longest prompt plus the full generation budget, so any
        # admitted request fits its table by construction (a speculative
        # step dispatched ahead of a row's last writes up to two
        # positions past its budget: the table covers them too)
        self._seq_ladder = seq_ladder.aligned(self._pool.page_size)
        self._max_context = self._seq_ladder.max_batch + self._max_new \
            + (self._spec + 1 if self._spec else 0)
        model_reach = getattr(model, "max_len", None)
        if model_reach is not None and self._max_context > model_reach:
            raise MXNetError(
                "DecodeServer: ladder top %d + max_new_tokens %d = "
                "%d positions exceeds the model's max_len %d — an "
                "out-of-range positional gather would silently clamp "
                "under jit and emit wrong tokens; shrink the ladder/"
                "budget or raise the model's reach"
                % (self._seq_ladder.max_batch, self._max_new,
                   self._max_context, model_reach))
        # the plain or the state step form over a layout that can, of a
        # model that declares ``chunk_lanes`` (a state model: that its
        # state can take a chunk): a prompt rides the decode step in
        # chunks and no prefill program is built; the block and
        # speculative forms, an int8 pool and a model that does not
        # declare it keep the whole-prompt prefill. A step's budget of
        # prompt tokens is twice the ladder's smallest rung, and the
        # mixed program is built at every rung within it (256 and 512 of
        # 256 / 512 / 1024 / 1536): a chunk takes the smallest that
        # holds what its prompt still has pending, so a short prompt
        # pays no dead lanes and a long one half the steps
        self._chunks = ()
        if not (self._block or self._spec) \
                and self._pool.layout.chunks \
                and getattr(model, "chunk_lanes", False):
            rungs = self._seq_ladder.buckets
            self._chunks = tuple(r for r in rungs if r <= 2 * rungs[0])
        self._chunk = self._chunks[-1] if self._chunks else 0
        self._max_pages = self._pool.pages_for(self._max_context)
        if self._max_pages > self._pool.usable_pages:
            raise MXNetError(
                "DecodeServer: one max-size request needs %d pages "
                "but the pool only has %d usable — raise "
                "MXNET_KV_POOL_PAGES or shrink the ladder/"
                "max_new_tokens" % (self._max_pages,
                                    self._pool.usable_pages))
        self._max_queue = max(1, int(max_queue))
        self._levels = max(1, envs.get_int("MXNET_SERVING_PRIORITIES"))
        self._default_deadline = (float(default_deadline_ms) / 1e3
                                  if default_deadline_ms is not None
                                  else None)
        self._record_every = int(record_every) if record_every \
            else envs.get_int("MXNET_SERVING_RECORD_EVERY")

        site = "decode" if not name else "decode:%s" % name
        self._site = site
        # donation makes each step update the pool in place on real
        # accelerators; the CPU PJRT client cannot donate (it would
        # only warn per compile), and correctness never depends on it
        donate = step_donate = chunk_donate = cow_donate = {}
        n_pool = len(self._pool.arrays)
        if jax.default_backend() not in ("cpu",):
            # (a state model's prefill is told its slot in front of them)
            first = 5 if self._state else 4
            donate = {"donate_argnums": tuple(range(first,
                                                    first + n_pool))}
            # the step's pools come after the fed-back token array and
            # its slots, neither of which it may consume
            # (a block step's after its block state and its rows' ends,
            # two arrays more)
            # (a state model's after its rows' slots and their number)
            first = 8 if self._block or self._state else 6
            step_donate = {"donate_argnums": tuple(range(first,
                                                         first + n_pool))}
            # (the mixed step's after its chunk, one array more)
            first += 1
            chunk_donate = {"donate_argnums": tuple(range(first,
                                                          first + n_pool))}
            cow_donate = {"donate_argnums": tuple(range(n_pool))}
        # ONE step program and one prefill program a rung, whatever the
        # kind of model: a block model's are the block forms, a
        # self-drafting model's the speculative ones. Over a mesh the
        # same functions under ``shard_map`` (``_over_mesh``): every chip
        # one program over its part of the parameters and the pools
        self._decode_prog = compile_watch.jit(
            self._block_decode_fn if self._block
            else self._spec_decode_fn if self._spec
            else self._over_mesh(self._state_decode_fn, 7) if self._state
            else self._decode_fn,
            "%s:step" % site,
            statics=(site, self._window, self._max_pages), **step_donate)
        # beside it, where prompts ride the step: the mixed programs, the
        # step's ``window`` lanes and a chunk's more — and no prefill
        self._chunk_progs = {
            C: compile_watch.jit(
                self._over_mesh(self._state_decode_fn_chunk, 8)
                if self._state else self._decode_fn_chunk,
                "%s:step:chunk:c%d" % (site, C),
                statics=(site, self._window, self._max_pages, C),
                **chunk_donate) for C in self._chunks}
        self._prefill_progs = {}
        for rung in () if self._chunks else self._seq_ladder.buckets:
            self._prefill_progs[rung] = compile_watch.jit(
                self._block_prefill_fn if self._block
                else self._spec_prefill_fn if self._spec
                else self._state_prefill_fn if self._state
                else self._prefill_fn,
                "%s:prefill:s%d" % (site, rung),
                statics=(site, "prefill", rung), **donate)
        # the copy-on-write page copy: one more fixed program, only
        # ever compiled when the prefix cache is on (warmup covers it)
        self._cow_prog = compile_watch.jit(
            self._over_mesh(self._cow_fn, 2, params=False, tokens=False),
            "%s:cow" % site, statics=(site, "cow"), **cow_donate)

        self._cond = threading.Condition()
        self._queue = deque()
        self._active = []
        self._params = _ParamsVersion(1, self._placed(params))
        self._rid = itertools.count(1)
        self._stats = {"requests": 0, "completed": 0, "cancelled": 0,
                       "timeouts": 0, "shed": 0, "errors": 0,
                       "preempted": 0, "prefill_steps": 0,
                       "decode_steps": 0, "decode_steps_ahead": 0,
                       "decode_faults": 0, "tokens_out": 0,
                       "queue_peak": 0, "swaps": 0,
                       "prefix_hits": 0, "prefix_misses": 0,
                       "prefix_hit_tokens": 0, "cow_splits": 0,
                       "cow_degraded": 0, "cross_preempts": 0,
                       "admitted": 0, "queue_wait_s": 0.0,
                       "prefill_s": 0.0, "decode_pages_live": 0,
                       "decode_pages_table": 0,
                       "readback_wait_s": 0.0,
                       "prefill_read_wait_s": 0.0,
                       "chunk_steps": 0, "chunk_tokens": 0}
        # every program the scheduler thread hands to the device takes
        # the next number (from 1, never reused; warm-up's take none):
        # the span that launches it says ``seq``, the span that waits
        # for it ``waits``, and a reader of the device's trace joins the
        # device's programs to them by order. Counted by program, with
        # the seconds inside the launch spans, from the spans' own stamps
        self._seq = 0
        self._launches = {"step": 0, "prefill": 0, "cow": 0}
        self._launch_s = {"step": 0.0, "prefill": 0.0, "cow": 0.0}
        self._shed_by_priority = {}
        self._counted = {}        # the model's step counters, summed
        # a block model's passes, summed over the rows of every step read
        self._blocks = {"denoise_passes": 0, "commit_passes": 0,
                        "fused_commits": 0, "tokens_unmasked": 0,
                        "blocks_committed": 0, "max_passes_a_block": 0}
        # a speculative model's steps, summed over the rows of every
        # step read
        self._specs = {"drafts_verified": 0, "drafts_accepted": 0,
                       "tokens_out": 0, "positions_run": 0}
        # the decode step that is dispatched and not yet read back (the
        # scheduler reads one step behind), why it was read before the
        # next was planned whenever it was (``stats()["decode_drains"]``),
        # a drain another thread asked for, and what a step with no
        # step before it is handed as ``prev``
        self._unread = None
        self._drains = {}
        self._drain_ask = None
        # (over a mesh the counters of every chip ride a step's output,
        # chip 0's first)
        n_counts = len(self._counters[1]) * self._shards \
            if self._counters else 0
        self._no_prev = self._placed(
            _np.zeros((self._window * (self._block + 2 if self._block
                                       else _SPEC_OUT if self._spec
                                       else 1) + n_counts,), _np.int32),
            whole=True)
        self._counted_by_chip = [{} for _ in range(self._shards)]
        ring = max(1, envs.get_int("MXNET_SERVING_LATENCY_RING"))
        self._intervals = deque(maxlen=ring)    # inter-token ms
        self._ttft = deque(maxlen=ring)         # submit -> first token
        self._steps_since_record = 0
        self._t0 = tracing.now()
        self._stopping = False
        self._drain = True
        self._closed = False
        self._started = False
        self._warming = False
        self._thread = None
        from .. import livemetrics
        livemetrics.register_decode_server(self)
        livemetrics.maybe_start()
        if start:
            self.start()

    @property
    def pool(self):
        """The :class:`KVCachePool` this server decodes against (its
        ``.arrays`` are the live device arrays, every one the programs
        carry)."""
        return self._pool

    # -- over a mesh -------------------------------------------------------
    def _pool_specs(self, layout):
        """One ``PartitionSpec`` a carried array of the pool
        (``kvcache.shard_specs``: pages and rings by key/value head)."""
        return tuple(kvcache.shard_specs(self._model, layout,
                                         self._model.axis))

    def _pool_shardings(self, layout):
        from jax.sharding import NamedSharding
        return [NamedSharding(self._mesh, spec)
                for spec in self._pool_specs(layout)]

    def _placed(self, tree, whole=False):
        """``tree`` on the server's device; over a mesh a parameter tree
        by the model's declaration (nothing moves where it already lies
        so), and what is ``whole`` on every chip."""
        import jax
        if self._mesh is None:
            return jax.device_put(tree, self._device)
        from jax.sharding import NamedSharding, PartitionSpec
        if whole:
            return jax.device_put(
                tree, NamedSharding(self._mesh, PartitionSpec()))
        return jax.device_put(tree, self._model.param_shardings())

    def _over_mesh(self, fn, n_host, params=True, tokens=True):
        """``fn`` as it is, or over a mesh ``fn`` under ``shard_map``: a
        program's arguments are the parameters (where it takes them),
        ``n_host`` arrays of the host's, whole on every chip, and the
        pool's arrays, each chip its heads' part; its results the
        step's token array (where it gives one), whole, and the pool's
        arrays."""
        if self._mesh is None:
            return fn
        import functools
        from jax.sharding import PartitionSpec as P
        pools = self._pool_specs(self._pool.layout)
        # (the page copy takes the pools first and the two page ids last)
        args = (P(),) * n_host + pools if params else pools + (P(),) * n_host
        out = ((P(),) if tokens else ()) + pools
        if params:
            mapped = self._model.on_mesh(fn, args, out)
        else:
            import jax
            mapped = jax.shard_map(fn, mesh=self._mesh, in_specs=args,
                                   out_specs=out, check_vma=False)
        # the program keeps its name: a reader of the device's trace
        # finds ``jit__state_decode_fn`` whatever runs it
        return functools.wraps(fn)(lambda *a: mapped(*a))

    def _greedy(self, logits):
        """The greedy token a row, ``(rows,)`` int32 — over a mesh the
        arg-max over the chips' (max, index) pairs, each of its own
        columns of the head: the logits are never gathered. Ties go to
        the lowest index, as ``argmax`` over the whole row would."""
        import jax
        import jax.numpy as jnp
        best = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if self._mesh is None:
            return best
        axis = self._model.axis
        top = jnp.take_along_axis(logits, best[:, None], axis=-1)[:, 0]
        best = best + jax.lax.axis_index(axis) * logits.shape[-1]
        tops = jax.lax.all_gather(top, axis)              # (chips, rows)
        bests = jax.lax.all_gather(best, axis)
        chip = jnp.argmax(tops, axis=0)
        return jnp.take_along_axis(bests, chip[None], axis=0)[0]

    def _counts_out(self, counts):
        """The model's step counters as they leave with the tokens: the
        one vector, or over a mesh every chip's, chip 0's first."""
        import jax
        import jax.numpy as jnp
        counts = counts.astype(jnp.int32).reshape(-1)
        if self._mesh is None:
            return counts
        return jax.lax.all_gather(counts, self._model.axis).reshape(-1)

    # -- compiled programs -------------------------------------------------
    # Three, for every kind of cache: ``pools`` is whatever the pool's
    # layout carries (``KVCachePool.arrays``), and the layout — the
    # pool's own object, found again from the model's declaration and
    # the arrays' dtype — attends and writes.
    def _prefill_fn(self, params, tokens, n_valid, page_table, *pools):
        import jax.numpy as jnp
        layout = kvcache.layout_for(self._model, pools)
        logits, *seqs = self._model.prefill(params, tokens)
        pools = layout.write_prefill(pools, page_table, seqs, n_valid)
        # greedy sampling in-program; only the token leaves the
        # device — returning the logits too would make XLA
        # materialize a dead (vocab,)-sized output per prefill
        last = jnp.take(logits[0], n_valid - 1, axis=0)
        token = jnp.argmax(last).astype(jnp.int32)
        return (token, *pools)

    def _decode_fn(self, params, tokens, positions, page_tables, prev,
                   src, *pools):
        """The step program. A row's input token is the one the step
        before computed, taken where it lies on the device: ``prev`` is
        that step's token array (not yet read back when this one is
        dispatched) and ``src[i]`` the slot row ``i`` had in it; ``-1``
        takes the host's ``tokens[i]`` (a row just admitted, a suffix
        feed, the first step after an empty window)."""
        import jax.numpy as jnp
        tokens = jnp.where(src >= 0, prev[jnp.maximum(src, 0)], tokens)
        return self._step_fn(params, tokens, positions, page_tables,
                             *pools)

    def _decode_fn_chunk(self, params, tokens, positions, page_tables,
                         prev, src, chunk, *pools):
        """The MIXED step program: :meth:`_decode_fn`'s ``window`` lanes
        and, behind them, ``C`` lanes that are ``C`` consecutive
        positions of ONE request's prompt — a decode step that carries a
        chunk, the weights streamed once for both. ``chunk`` is the
        host's: the lanes' tokens ``(C,)``, the request's page-table row
        ``(max_pages,)``, the position of lane 0, how many lanes are
        live and the request's slot among the step's rows. The model's
        ``decode`` runs ``window + C`` rows — row-wise in everything but
        ``attend``, which the layout splits (``attend_chunk``: the
        decode rows keep their paged kernel, chunk lane ``j`` sees the
        request's pages before the chunk and the chunk's own rows ``<=
        j``) — and only ``window + 1`` of them reach the head: the
        decode rows and the chunk's last live lane, whose argmax is the
        request's first token once the chunk is its prompt's last. It is
        put in the request's own slot of the ``(window,)`` token array,
        so that ``prev`` keeps one shape and the next step takes it by
        ``src`` on the device. Dead lanes (a last chunk shorter than
        ``C``) write nothing and choose no expert."""
        import jax.numpy as jnp
        B, M = self._window, self._max_pages
        C = chunk.shape[0] - M - 3
        tokens = jnp.where(src >= 0, prev[jnp.maximum(src, 0)], tokens)
        table, (start, n, slot) = chunk[C:C + M], chunk[C + M:]
        lanes = jnp.arange(C, dtype=jnp.int32)
        rows = jnp.arange(B, dtype=jnp.int32)
        layout = kvcache.layout_for(self._model, pools)
        attend = layout.attend_chunk(pools, page_tables, positions, table,
                                     start)
        logits, *new = self._model.decode(
            params, jnp.concatenate([tokens, chunk[:C]]),
            jnp.concatenate([positions, start + lanes]), attend,
            head=jnp.concatenate([rows, B + jnp.maximum(n, 1)[None] - 1]),
            live=jnp.concatenate([jnp.ones((B,), bool), lanes < n]))
        n_arrays = len(layout.specs)
        pools = layout.write_tokens(
            pools, page_tables, positions,
            [a[:, :B] for a in new[:n_arrays]],
            getattr(self._model, "use_pallas", False))
        pools = layout.write_chunk(
            pools, table, start, n, [a[:, B:] for a in new[:n_arrays]])
        out = jnp.argmax(logits, axis=-1).astype(jnp.int32)    # (B + 1,)
        tokens_out = jnp.where(rows == slot, out[B], out[:B])
        if len(new) > n_arrays:
            tokens_out = jnp.concatenate(
                [tokens_out, new[-1].astype(jnp.int32).reshape(-1)])
        return (tokens_out, *pools)

    def _step_fn(self, params, tokens, positions, page_tables, *pools):
        import jax.numpy as jnp
        layout = kvcache.layout_for(self._model, pools)
        attend = layout.attend(pools, page_tables, positions)
        logits, *new = self._model.decode(
            params, tokens, positions, attend)
        pools = layout.write_tokens(
            pools, page_tables, positions, new,
            getattr(self._model, "use_pallas", False))
        # only the argmax tokens leave the device: a (window, vocab)
        # logits output would be dead weight on the per-token hot path
        tokens_out = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if len(new) > len(layout.specs):
            # the model's step counters leave with the tokens, one array
            tokens_out = jnp.concatenate(
                [tokens_out, new[-1].astype(jnp.int32).reshape(-1)])
        return (tokens_out, *pools)

    # -- the block forms (a model with ``block_length``) ------------------
    def _check_block_model(self):
        """What a block model cannot do is refused here, when the server
        is built, with a typed error — never a wrong token later."""
        B, S = self._block, self._pool.page_size
        if S % B:
            raise MXNetError(
                "DecodeServer: page_size %d is no multiple of the model's "
                "block_length %d — a block's rows are written into ONE "
                "page" % (S, B))
        if not self._pool.layout.blocks:
            raise MXNetError(
                "DecodeServer: a block model (block_length %d) cannot "
                "run over a %s pool: a commit writes %d rows a row, which "
                "an int8 page would have to requantize, and the block "
                "form of a latent pool is causal (a speculative step's), "
                "not the all-see-all block of a diffusion model — give it "
                "a float per-head pool"
                % (B, type(self._pool.layout).__name__, B))
        if self._prefix_on:
            raise MXNetError(
                "DecodeServer: prefix sharing feeds a prompt's un-cached "
                "suffix through the step ONE token at a time, which a "
                "block model (block_length %d) has no step for — build "
                "it with prefix_cache=False" % B)

    def _block_prefill_fn(self, params, tokens, n_valid, page_table,
                          *pools):
        """A block model's prefill: the whole blocks of the prompt
        (``n_valid`` tokens, a multiple of the block length) in one
        block-causal pass, their keys and values written. It emits NO
        token — the first block of the answer is denoised like every
        other — so only the pools come back."""
        layout = kvcache.layout_for(self._model, pools)
        _logits, *seqs = self._model.prefill(params, tokens)
        return layout.write_prefill(pools, page_table, seqs, n_valid)

    def _block_decode_fn(self, params, x, state, positions, ends,
                         page_tables, prev, src, *pools):
        """The block-step program: every row runs ONE pass over ``2 x
        block_length`` positions, ``[p, p + 2B)`` for a row whose block
        starts at ``p = positions[i]``, and what the program finds in the
        row's state says what the pass is. ``x (D, B)`` are the block's
        tokens as they stand, ``state (D,)`` the bits of the positions
        still masked (-1: no row), ``ends (D,)`` the position the row
        ends at (prompt + ``max_new``); a row whose state the host does
        not know yet — the step before it, still unread, was a denoising
        pass under a rule that decides on the device how many positions
        it unmasks — takes ``x`` and ``state`` from that step's output
        where it lies, ``prev`` at slot ``src[i]``.

        - A row with something masked DENOISES its block in the first
          half: the model's own rule (``model.unmask``) unmasks positions
          from the pass's logits and nothing is written (its rows go to
          the dump page). Its second half is dead: it attends nothing,
          costs no expert and does not reach the head.
        - A row with nothing masked COMMITS its block in the first half —
          the block's keys and values are written at its positions, in
          every layer before the layer attends — and, in the same pass,
          the FRESH block at ``p + B`` has its first denoising pass in the
          second half: all MASK in, attending the row's committed keys,
          the block just committed and its own; the rule unmasks on the
          second half's logits. The row leaves the pass as the block at
          ``p + B`` after one denoising pass.
        - Where ``p + B`` is at or past the row's end there is no fresh
          block: the commit runs by itself, the second half dead (what
          the fused form degenerates to; the scheduler never plans it —
          a row's last block ends the request when it settles — but a
          step dispatched ahead of that read-back runs it).

        Only the ``B`` positions a row that the rule reads reach the head.
        Out, a row: the tokens of the block that is now the row's, the
        bits still masked, the kind of pass (1 denoised, 2 committed, 3
        committed and denoised the block after, 0 no row); then the
        model's counters."""
        import jax.numpy as jnp
        D, B = self._window, self._block
        fed = prev[:D * (B + 2)].reshape(D, B + 2)[jnp.maximum(src, 0)]
        x = jnp.where((src >= 0)[:, None], fed[:, :B], x)
        state = jnp.where(src >= 0, fed[:, B], state)
        bit = jnp.arange(B, dtype=jnp.int32)
        masked = ((jnp.maximum(state, 0)[:, None] >> bit) & 1).astype(bool)
        commit = state == 0
        fresh = jnp.logical_and(commit, positions + B < ends)
        opened = jnp.full((D, B), self._model.mask_token_id, x.dtype)
        live = jnp.repeat(jnp.stack([state >= 0, fresh], axis=1), B, axis=1)
        layout = kvcache.layout_for(self._model, pools)
        attend = layout.block_step(
            pools, page_tables, positions, commit, fresh,
            getattr(self._model, "use_pallas", False))
        logits, *new = self._model.decode_block(
            params, jnp.concatenate([x, opened], axis=1), positions, attend,
            live=live, head=fresh.astype(jnp.int32))
        x_new, still = self._model.unmask(
            logits, jnp.where(fresh[:, None], opened, x),
            jnp.logical_or(fresh[:, None], masked))
        left = jnp.sum(still.astype(jnp.int32) << bit, axis=1)
        kind = jnp.where(state < 0, 0,
                         jnp.where(fresh, 3, jnp.where(commit, 2, 1)))
        out = jnp.concatenate(
            [x_new.astype(jnp.int32),
             jnp.where(state < 0, -1, left)[:, None],
             kind[:, None]], axis=1).reshape(-1)
        if len(new) > len(layout.specs):
            out = jnp.concatenate(
                [out, new[-1].astype(jnp.int32).reshape(-1)])
        return (out, *attend.pools)

    # -- the speculative forms (a model with ``draft_length``) -------------
    def _check_spec_model(self):
        """What a self-drafting model cannot do is refused here, when the
        server is built, with a typed error — never a wrong token later."""
        if self._spec != 1:
            raise MXNetError(
                "DecodeServer: draft_length %d — the speculative step "
                "verifies ONE draft a row (two positions)" % self._spec)
        if not self._pool.layout.causal_blocks:
            raise MXNetError(
                "DecodeServer: a self-drafting model (draft_length %d) "
                "cannot run over a %s pool: its step attends and writes "
                "%d consecutive positions a row, causal among themselves, "
                "and that block form exists for a float latent pool only "
                "(an int8 page would have to requantize)"
                % (self._spec, type(self._pool.layout).__name__,
                   self._spec + 1))
        if self._prefix_on:
            raise MXNetError(
                "DecodeServer: prefix sharing feeds a prompt's un-cached "
                "suffix through the step ONE token at a time and leaves "
                "the drafter's cache unfilled, which a self-drafting model "
                "(draft_length %d) has no step for — build it with "
                "prefix_cache=False" % self._spec)

    def _spec_prefill_fn(self, params, tokens, n_valid, page_table,
                         *pools):
        """A self-drafting model's prefill: the main model over the
        prompt, then the drafter over it — position ``i`` reads the main
        model's state at ``i`` and the token after it, the last one the
        first token the prefill itself emits. Both caches are written;
        out, the first token and the first draft."""
        import jax.numpy as jnp
        layout = kvcache.layout_for(self._model, pools)
        n = len(layout.specs)
        logits, hidden, *seqs = self._model.prefill_draft(params, tokens)
        token = jnp.argmax(jnp.take(logits[0], n_valid - 1, axis=0)) \
            .astype(jnp.int32)
        after = jnp.roll(tokens, -1, axis=1).at[0, n_valid - 1].set(token)
        d_logits, *d_seqs = self._model.draft_prefill(params, hidden, after)
        draft = jnp.argmax(jnp.take(d_logits[0], n_valid - 1, axis=0)) \
            .astype(jnp.int32)
        seqs = [jnp.concatenate([a, b]) for a, b in zip(seqs[:n], d_seqs)]
        pools = layout.write_prefill(pools, page_table, seqs, n_valid)
        return (jnp.stack([token, draft]), *pools)

    def _spec_decode_fn(self, params, tokens, positions, page_tables,
                        prev, src, *pools):
        """The speculative step program (module docstring): every row
        verifies its draft and drafts the next. ``tokens (D, 2)`` are
        ``[x, d]``, the row's last confirmed token and its draft for
        the position after, ``positions (D,)`` x's; a row whose step
        before is unread takes all three from that step's output where
        it lies, ``prev`` at slot ``src[i]`` — the host does not know
        whether that step accepted. Out, a row: ``y1, y2, accepted, next
        draft, next position``; then the model's counters."""
        import jax.numpy as jnp
        D = self._window
        fed = prev[:D * _SPEC_OUT].reshape(D, _SPEC_OUT)[jnp.maximum(src, 0)]
        ahead = src >= 0
        x = jnp.where(ahead, jnp.where(fed[:, 2] > 0, fed[:, 1], fed[:, 0]),
                      tokens[:, 0])
        d = jnp.where(ahead, fed[:, 3], tokens[:, 1])
        positions = jnp.where(ahead, fed[:, 4], positions)
        layout = kvcache.layout_for(self._model, pools)
        n = len(layout.specs)
        attend = layout.attend_causal(pools, page_tables, positions)
        logits, hidden, *new = self._model.verify(
            params, jnp.stack([x, d], axis=1), positions, attend)
        y = jnp.argmax(logits, axis=-1).astype(jnp.int32)      # (D, 2)
        accepted = y[:, 0] == d
        d_logits, *d_new = self._model.draft(
            params, hidden, y, positions, attend)
        drafts = jnp.argmax(d_logits, axis=-1).astype(jnp.int32)
        pools = layout.write_causal(
            pools, page_tables, positions,
            [jnp.concatenate([a, b]) for a, b in zip(new[:n], d_new)],
            getattr(self._model, "use_pallas", False))
        out = jnp.stack(
            [y[:, 0], y[:, 1], accepted.astype(jnp.int32),
             jnp.where(accepted, drafts[:, 1], drafts[:, 0]),
             positions + 1 + accepted], axis=1).reshape(-1)
        if len(new) > n:
            # the two passes' counters as one: a name that starts with
            # ``max`` keeps the larger, the others add up
            a = new[-1].astype(jnp.int32).reshape(-1)
            b = d_new[-1].astype(jnp.int32).reshape(-1)
            largest = _np.asarray([name.startswith("max")
                                   for name in self._counters[1]])
            out = jnp.concatenate(
                [out, jnp.where(largest, jnp.maximum(a, b), a + b)])
        return (out, *pools)

    # -- the state forms (a model with ``state_arrays``) -------------------
    def _check_state_model(self):
        """What fixed state a row makes impossible today is refused
        here, when the server is built, with a typed error — never a
        wrong token later."""
        what = "a model that keeps fixed state a row (state_arrays)"
        if self._block or self._spec:
            raise MXNetError(
                "DecodeServer: %s cannot be served in the %s form: a pass "
                "over several positions a row moves the state at each, "
                "and the state after each position is not kept until the "
                "step knows which of them stand"
                % (what, "block" if self._block else "speculative"))
        if self._pool.dtype == "int8":
            raise MXNetError(
                "DecodeServer: %s over an int8 pool — int8 pages with "
                "per-page scales exist beside no state" % what)
        if self._prefix_on:
            raise MXNetError(
                "DecodeServer: prefix sharing hands a later prompt the "
                "PAGES of a shared prefix, and %s would need the state as "
                "it stood at that page boundary, which the index does not "
                "hold — build it with prefix_cache=False" % what)
        if self._pool.state_rows != self._window:
            raise MXNetError(
                "DecodeServer: the pool holds state for %d rows, the "
                "window is %d — a step works on every row of the state "
                "arrays" % (self._pool.state_rows, self._window))

    def _state_prefill_fn(self, params, tokens, n_valid, page_table, slot,
                          *pools):
        """A state model's prefill: :meth:`_prefill_fn`, and the state
        after the prompt's TRUE length written whole into row ``slot``
        of the state arrays (nothing where ``n_valid`` is 0: a
        warm-up)."""
        import jax.numpy as jnp
        layout = kvcache.layout_for(self._model, pools)
        n = len(layout.specs)
        logits, *out = self._model.prefill(
            params, tokens, jnp.reshape(n_valid, (1,)))
        pages = layout.write_prefill(pools, page_table, out[:n], n_valid)
        state = layout.write_state(pools, slot, out[n:], n_valid > 0)
        last = jnp.take(logits[0], n_valid - 1, axis=0)
        token = jnp.argmax(last).astype(jnp.int32)
        return (token, *pages, *state)

    def _state_decode_fn(self, params, tokens, positions, slots, n_live,
                         page_tables, prev, src, *pools):
        """A state model's step program: :meth:`_decode_fn`, the model
        handed the state of the window's rows — row ``i`` of the step
        works on row ``slots[i]`` of the state arrays, the first
        ``n_live`` of them live — and returning it among its results."""
        import jax.numpy as jnp
        tokens = jnp.where(src >= 0, prev[jnp.maximum(src, 0)], tokens)
        layout = kvcache.layout_for(self._chip_model, pools)
        n, n_state = len(layout.specs), len(layout.state)
        attend = layout.attend(pools, page_tables, positions)
        state = layout.row_state(
            pools, slots, jnp.arange(self._window) < n_live)
        logits, *new = self._chip_model.decode(
            params, tokens, positions, attend, state)
        pages = layout.write_tokens(
            pools, page_tables, positions, new[:n],
            getattr(self._chip_model, "use_pallas", False))
        tokens_out = self._greedy(logits)
        if len(new) > n + n_state:
            tokens_out = jnp.concatenate(
                [tokens_out, self._counts_out(new[-1])])
        return (tokens_out, *pages, *new[n:n + n_state])

    def _state_decode_fn_chunk(self, params, tokens, positions, slots,
                               n_live, page_tables, prev, src, chunk,
                               *pools):
        """A state model's MIXED step program: :meth:`_state_decode_fn`'s
        ``window`` lanes and, behind them, the ``C`` lanes of ONE
        request's chunk, as :meth:`_decode_fn_chunk` is beside
        :meth:`_decode_fn` (``chunk``, the head, the request's first
        token: as there). The chunk's request is NOT one of the step's
        live rows — its row of the state arrays, ``slots[chunk's slot]``,
        rides behind ``n_live`` — so the model writes that row from the
        chunk's lanes alone (``decode(..., chunk=(the row, start,
        n))``)."""
        import jax.numpy as jnp
        B, M = self._window, self._max_pages
        C = chunk.shape[0] - M - 3
        tokens = jnp.where(src >= 0, prev[jnp.maximum(src, 0)], tokens)
        table, (start, n, slot) = chunk[C:C + M], chunk[C + M:]
        lanes = jnp.arange(C, dtype=jnp.int32)
        rows = jnp.arange(B, dtype=jnp.int32)
        layout = kvcache.layout_for(self._chip_model, pools)
        n_arrays, n_state = len(layout.specs), len(layout.state)
        attend = layout.attend_chunk(pools, page_tables, positions, table,
                                     start)
        state = layout.row_state(pools, slots, rows < n_live)
        logits, *new = self._chip_model.decode(
            params, jnp.concatenate([tokens, chunk[:C]]),
            jnp.concatenate([positions, start + lanes]), attend, state,
            head=jnp.concatenate([rows, B + jnp.maximum(n, 1)[None] - 1]),
            live=jnp.concatenate([state.live, lanes < n]),
            chunk=(slots[jnp.maximum(slot, 0)], start, n))
        held = tuple(new[n_arrays:n_arrays + n_state])
        pages = layout.write_tokens(
            pools, page_tables, positions,
            [a[:, :B] for a in new[:n_arrays]],
            getattr(self._chip_model, "use_pallas", False))
        pages = layout.write_chunk(
            pages + held, table, start, n,
            [a[:, B:] for a in new[:n_arrays]])
        out = self._greedy(logits)                           # (B + 1,)
        tokens_out = jnp.where(rows == slot, out[B], out[:B])
        if len(new) > n_arrays + n_state:
            tokens_out = jnp.concatenate(
                [tokens_out, self._counts_out(new[-1])])
        return (tokens_out, *pages, *held)

    # copy-on-write page copy — the whole split is one traced program
    # (src/dst ride as traced scalars, so any page pair reuses it).
    # Axis 1 of every carried array is the page, so an int8 page's
    # scales go with it and the private copy dequantizes bit-identically
    def _cow_fn(self, *args):
        *pools, src, dst = args
        return tuple(pages.at[:, dst].set(pages[:, src])
                     for pages in pools)

    def _namespace(self, ver):
        """The prefix-index namespace: share group (defaults to this
        server's unique pool attachment, so co-tenant models never
        alias by accident) + weight generation (swapped weights
        compute different K/V for the same tokens)."""
        return (self._share_group or self._owner, ver.version)

    def _pool_preempt_cb(self):
        """A co-tenant's :meth:`KVCachePool.request_preempt` give-back
        ask. Runs on the REQUESTER's thread, so it only schedules: the
        victim's own scheduler preempts one of its active requests on
        its next tick (pages must never be touched cross-thread)."""
        with self._cond:
            if self._closed or self._stopping or not self._active:
                return False
            self._preempt_asks += 1
            self._stats["cross_preempts"] += 1
            self._cond.notify_all()
        return True

    def _adopt_pool(self, out):
        """Re-point the pool at a step program's functionally-updated
        arrays; returns the program's remaining (token) outputs."""
        n = len(self._pool.arrays)
        self._pool.arrays[:] = out[-n:]
        return out[:-n]

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        if self._started:
            return self
        if self._closed:
            raise ServerClosedError("DecodeServer already stopped")
        self._started = True
        self._t0 = tracing.now()
        self._thread = threading.Thread(
            target=self._loop, name="mxnet-decode-scheduler",
            daemon=True)
        self._thread.start()
        return self

    def stop(self, drain=True):
        """Stop the server. ``drain=True`` finishes every queued and
        active generation first; ``drain=False`` fails them with
        ServerClosedError and reclaims their pages. Either way every
        outstanding stream TERMINATES — a consumer blocked in
        ``tokens()`` sees the stream end or the typed error, never a
        hang: the scheduler join is bounded by
        ``MXNET_DECODE_STOP_TIMEOUT_MS``, and a scheduler wedged past
        it (a planned ``serve_decode`` hang, a stuck model call)
        degrades the stop to the non-draining path so in-flight
        requests still fail with ServerClosedError and their pages
        come back through the counted reclaim. Emits a final
        ``decode`` telemetry record."""
        if self._closed:
            return
        with self._cond:
            self._stopping = True
            self._drain = drain
            self._cond.notify_all()
        if self._started:
            join_s = max(
                envs.get_int("MXNET_DECODE_STOP_TIMEOUT_MS"), 1) / 1e3
            self._thread.join(join_s)
            if self._thread.is_alive():
                # wedged scheduler: it can no longer be trusted to
                # retire work, so the typed-error path below does —
                # _complete is first-wins, so the scheduler waking up
                # later and retiring the same requests is benign
                drain = False
                with self._cond:
                    self._drain = False
        elif drain:
            while self._has_work():
                self._tick()
        if not drain:
            with self._cond:
                doomed = list(self._queue) + list(self._active)
                self._queue.clear()
                del self._active[:]
            for r in doomed:
                self._finish(r, ServerClosedError(
                    "server stopped; request %s dropped"
                    % r.request_id))
            if not (self._started and self._thread.is_alive()):
                # the step a non-draining stop found unread: its rows
                # have just failed, so its output is dropped like that
                # of any row ended while its step was unread
                self._read_unread("stop")
        self._closed = True
        # NOTE: the prefix index is NOT released here — on a shared
        # pool the surviving co-tenant servers keep hitting the cached
        # prefixes (that is the failover story); an owned pool dies
        # with the server anyway
        self._emit_record()    # final record still shows our tenancy
        self._pool.detach(self._owner)
        from .. import livemetrics
        livemetrics.deregister_decode_server(self)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    def warmup(self):
        """Compile the whole fixed program set (every prefill rung +
        the decode step; where prompts ride the step in chunks, the step
        and the mixed step, and no prefill) before taking traffic, so no
        live request ever pays an XLA compile. Warmup traffic writes only
        the dump
        page (``n_valid=0``, all-zero tables), so the pool's logical
        content is untouched; the returned pools are adopted (the
        programs may donate their pool inputs on real accelerators).
        The scheduler is paused for the duration — warmup and a live
        step must never race on the pool arrays (requests submitted
        meanwhile just wait). Returns the number of programs
        readied."""
        import jax
        with self._cond:
            if self._closed:
                raise ServerClosedError("DecodeServer is stopped")
            self._warming = True
        try:
            n = 0
            zeros_pt = _np.zeros((self._max_pages,), _np.int32)
            # a state model's prefill is told a slot; it writes nothing
            slot = (_np.int32(0),) if self._state else ()
            with self._pool.step_lock:
                for rung in self._prefill_progs:
                    toks = _np.zeros((1, rung), _np.int32)
                    out = self._prefill_progs[rung](
                        self._params.tree, toks, _np.int32(0),
                        zeros_pt, *slot, *self._pool.arrays)
                    jax.block_until_ready(out[0])
                    self._adopt_pool(out)
                    n += 1
                pos = _np.zeros((self._window,), _np.int32)
                pts = _np.zeros((self._window, self._max_pages),
                                _np.int32)
                src = _np.full((self._window,), -1, _np.int32)
                if self._block:
                    # no rows (state -1): every write goes to the dump page
                    feed = (_np.zeros((self._window, self._block),
                                      _np.int32),
                            _np.full((self._window,), -1, _np.int32), pos,
                            pos)
                elif self._spec:
                    feed = (_np.zeros((self._window, 2), _np.int32), pos)
                elif self._state:
                    # no live row: every row's state stays as it is
                    feed = (pos, pos,
                            _np.arange(self._window, dtype=_np.int32),
                            _np.int32(0))
                else:
                    feed = (_np.zeros((self._window,), _np.int32), pos)
                # twice: fed nothing, then fed its own token array, the
                # two kinds of ``prev`` a live step is handed
                # (the mixed programs too, told a chunk of no live lane
                # for no row: it writes nothing)
                for C, prog in ((0, self._decode_prog),
                                *self._chunk_progs.items()):
                    chunk = ()
                    if C:
                        idle = _np.zeros((C + self._max_pages + 3,),
                                         _np.int32)
                        idle[-1] = -1
                        chunk = (idle,)
                    prev = self._no_prev
                    for _ in range(2):
                        out = prog(self._params.tree, *feed, pts, prev,
                                   src, *chunk, *self._pool.arrays)
                        jax.block_until_ready(out[0])
                        prev = self._adopt_pool(out)[0]
                    n += 1
                if self._prefix_on:
                    # the COW copy joins the fixed set only when the
                    # prefix cache can actually trigger it; dump page
                    # onto itself = a logical no-op
                    out = self._cow_prog(*self._pool.arrays,
                                         _np.int32(0), _np.int32(0))
                    jax.block_until_ready(out[0])
                    self._adopt_pool(out)
                    n += 1
            return n
        finally:
            with self._cond:
                self._warming = False
                self._cond.notify_all()

    # -- admission ---------------------------------------------------------
    def submit(self, prompt, *, max_new_tokens=None, priority=0,
               deadline_ms=None, eos_id=None, trace_ctx=None):
        """Admit one generation: ``prompt`` is a 1-D int token array
        (length <= the ladder top). Returns a :class:`DecodeRequest`
        future streaming up to ``max_new_tokens`` greedy tokens
        (stopping early at ``eos_id``). ``priority`` (0 lowest ..
        ``MXNET_SERVING_PRIORITIES``-1) participates in overload
        shedding — a full queue sheds its newest lowest-class member
        below the arrival instead of the arrival itself — and in
        KV-pool preemption. ``deadline_ms`` bounds the WHOLE
        generation: a request that ages past it (queued or streaming)
        fails with RequestTimeoutError and frees its pages.
        ``trace_ctx`` is an optional :func:`tracing.wire_context` dict
        from the submitting process (the fleet router passes one) —
        when tracing is on here too, it is adopted so the request's
        queue/prefill/decode spans carry the ORIGIN request_id and
        merge causally with the submitter's trace."""
        if self._closed:
            raise ServerClosedError("DecodeServer is stopped")
        prompt = _np.asarray(prompt)
        if prompt.ndim != 1 or prompt.size < 1:
            raise MXNetError(
                "DecodeServer.submit: prompt must be a non-empty 1-D "
                "token array, got shape %s" % (prompt.shape,))
        prompt = prompt.astype(_np.int32)
        if len(prompt) > self._seq_ladder.max_batch:
            raise MXNetError(
                "DecodeServer.submit: prompt length %d exceeds the "
                "ladder top %d" % (len(prompt),
                                   self._seq_ladder.max_batch))
        max_new = int(max_new_tokens) if max_new_tokens is not None \
            else self._max_new
        if not 1 <= max_new <= self._max_new:
            raise MXNetError(
                "DecodeServer.submit: max_new_tokens must be in "
                "1..%d (the server budget), got %d"
                % (self._max_new, max_new))
        priority = validate_priority(priority, self._levels)
        fault.inject("serve_admit")
        deadline_s = (float(deadline_ms) / 1e3
                      if deadline_ms is not None
                      else self._default_deadline)
        rid = "d%06d" % next(self._rid)
        req = DecodeRequest(prompt, max_new, priority,
                            req_deadline(deadline_s), eos_id, rid)
        if tracing.enabled():
            joined = rid
            args = {"server_request_id": rid}
            if trace_ctx:
                adopted = tracing.adopt_context(
                    trace_ctx, name="ctx:submit", cat="wire",
                    tid=tracing.track("req %s"
                                      % trace_ctx.get("request_id", rid)))
                if adopted and adopted.get("request_id"):
                    joined = adopted["request_id"]
            args["request_id"] = joined
            req.trace_args = args
            req._t_trace = req.t_submit
        victim = None
        shed = stopping = False
        with self._cond:
            if self._stopping:
                stopping = True
            else:
                self._stats["requests"] += 1
                if len(self._queue) >= self._max_queue:
                    victim = shed_lowest_locked(self._queue, priority)
                    if victim is None:
                        self._stats["shed"] += 1
                        self._note_shed_locked(priority)
                        shed = True
                    else:
                        self._stats["shed"] += 1
                        self._note_shed_locked(victim.priority)
                if not shed:
                    self._queue.append(req)
                    if len(self._queue) > self._stats["queue_peak"]:
                        self._stats["queue_peak"] = len(self._queue)
                    self._cond.notify_all()
        if stopping:
            raise ServerClosedError(
                "DecodeServer is stopping; request %s not admitted"
                % rid)
        if victim is not None:
            telemetry.note("decode_shed")
            profiler.increment_counter("decode_shed")
            victim._complete(ServerOverloadedError(
                "decode: request %s (priority %d) shed for a "
                "priority-%d arrival — queue full (max_queue=%d)"
                % (victim.request_id, victim.priority, priority,
                   self._max_queue)))
        if shed:
            telemetry.note("decode_shed")
            profiler.increment_counter("decode_shed")
            raise ServerOverloadedError(
                "decode: request %s (priority %d) shed — queue full "
                "(max_queue=%d) and no lower-priority request to "
                "displace; retry with backoff or raise max_queue"
                % (rid, priority, self._max_queue))
        return req

    def _note_shed_locked(self, priority):
        self._shed_by_priority[priority] = \
            self._shed_by_priority.get(priority, 0) + 1

    # -- weight hot-swap ---------------------------------------------------
    def swap_weights(self, params=None, *, prefix=None, epoch=None,
                     validate=True):
        """Zero-downtime weight swap: load the new tree alongside the
        old, flip atomically between steps. ``params`` is a tree
        matching the serving one (same structure, shapes, dtypes — a
        swap must never recompile); or ``prefix``/``epoch`` name a
        checkpoint manifest (``checkpoint.load_param_arrays`` — the
        topology-neutral format makes this pure placement). In-flight
        requests finish on the weights they started with; requests
        admitted after the flip use the new ones; the old tree frees
        when its last request drains. Returns the new version
        number."""
        import jax
        if (params is None) == (prefix is None):
            raise MXNetError(
                "swap_weights: pass exactly one of params= or "
                "prefix=/epoch=")
        if params is None:
            from .. import checkpoint
            params = checkpoint.load_param_arrays(prefix, epoch,
                                                  validate=validate)
        cur = self._params.tree
        cur_leaves, cur_def = jax.tree_util.tree_flatten(cur)
        try:
            new_leaves, new_def = jax.tree_util.tree_flatten(params)
        except Exception as exc:
            raise MXNetError("swap_weights: not a parameter tree "
                             "(%s)" % exc)
        if new_def != cur_def:
            raise MXNetError(
                "swap_weights: parameter tree structure differs from "
                "the serving one (%s vs %s) — a swap replaces values, "
                "never architecture" % (new_def, cur_def))
        for old, new in zip(cur_leaves, new_leaves):
            if tuple(old.shape) != tuple(_np.shape(new)) or \
                    _np.dtype(old.dtype) != _np.dtype(
                        getattr(new, "dtype", _np.asarray(new).dtype)):
                raise MXNetError(
                    "swap_weights: leaf shape/dtype mismatch (%s/%s "
                    "vs %s/%s) — same shapes = same programs; a swap "
                    "must never recompile"
                    % (tuple(_np.shape(new)),
                       _np.dtype(getattr(new, "dtype",
                                         _np.asarray(new).dtype)),
                       tuple(old.shape), _np.dtype(old.dtype)))
        new_tree = self._placed(params)
        # fully materialize the new generation BEFORE the flip: the
        # next step must never block on a half-loaded tree
        jax.block_until_ready(jax.tree_util.tree_leaves(new_tree))
        with self._cond:
            old = self._params
            new_version = old.version + 1
            self._params = _ParamsVersion(new_version, new_tree)
            self._stats["swaps"] += 1
            # the scheduler reads its unread step before it plans with
            # two generations alive
            self._drain_ask = "swap_weights"
        if self._prefix_on:
            # the old generation's cached prefixes can never be hit
            # again (the namespace carries the version) — release the
            # index's references so the pages come back
            self._pool.prefix_release(self._namespace(old))
        telemetry.note("decode_weight_swaps")
        profiler.increment_counter("decode_weight_swaps")
        return new_version

    # -- scheduler ---------------------------------------------------------
    def _has_work(self):
        with self._cond:
            return not self._idle_locked()

    def _idle_locked(self):
        """Nothing queued, nothing active, no step unread."""
        return not self._queue and not self._active \
            and self._unread is None

    def _loop(self):
        while True:
            with self._cond:
                # idle = no queued/active work (or warmup owns the
                # pool): a plain long wait — submit/stop/warmup-end
                # all notify, the 1 s belt only backstops a lost wake
                # (a step still unread when warmup begins is read
                # first: the tick does that and nothing else)
                while not self._stopping and (
                        self._idle_locked() or
                        (self._warming and self._unread is None)):
                    with tracing.span("decode.wait"):
                        self._cond.wait(1.0)
                if self._stopping and (not self._drain
                                       or self._idle_locked()):
                    break
            if not self._tick():
                # head-of-line blocked (pool pressure) or a reap-only
                # pass: don't spin hot
                with self._cond, tracing.span("decode.wait"):
                    self._cond.wait(0.002)

    def _tick(self):
        """One scheduler pass: reap cancellations/deadlines, admit at
        most ONE request, dispatch ONE decode step over every active
        request — where prompts ride the step, with up to ``chunk``
        tokens of the head-most pending prompt on the mixed program's
        lanes — then read back and hand out the tokens of the step
        dispatched a pass EARLIER — the interleave that keeps decode
        from starving behind bursts of prompts, one step ahead of the
        host. In the device's order: build N+1 → dispatch N+1 → read
        back N → emit N → record → reap / admit / pages → build N+2;
        everything the host does with step N's tokens, and the next
        launch, run while step N+1 does. What is planned counts the
        token in flight (``DecodeRequest.unread``): positions, the page
        a write lands in, the end by ``max_new``. Where a form keeps
        the prefill program, it is dispatched behind the step in flight
        and its first token read at once (it is the request's time to
        first token); a prompt that rides the step launches nothing at
        admission, and its first token is read with its last chunk's
        step. Returns True when any step ran or was read."""
        with self._cond:
            warming = self._warming
        if warming:                    # warmup owns the pool arrays:
            self._read_unread("warmup")     # nothing is left in flight
            return False
        with self._cond:
            asks = self._preempt_asks
            self._preempt_asks = 0
            active, queued = len(self._active), len(self._queue)
        with tracing.span("decode.tick", active=active, queued=queued):
            with tracing.span("decode.reap"):
                # service co-tenant give-back asks FIRST: preempting
                # one of our own active requests frees pages a
                # higher-pool-priority model is starving for (its
                # alloc retries on its next tick)
                for _ in range(asks):
                    victim = self._pick_victim(below=self._levels)
                    if victim is None:
                        break
                    self._preempt(victim)
                self._reap()
            did = self._admit_one()
            did = self._decode_once() or did
            with tracing.span("decode.record"):
                if metering.enabled():
                    # integrate KV page holdings at the step boundary:
                    # each active request's pages x dt accrue to its
                    # tenant AND to the meter's pool total in one
                    # dual-entry pass
                    with self._cond:
                        entries = [
                            (metering.inner_key(self, r.request_id),
                             len(r.pages)) for r in self._active]
                    metering.request_pages(entries, time.monotonic())
                if did:
                    self._steps_since_record += 1
                    if self._steps_since_record >= self._record_every:
                        self._steps_since_record = 0
                        self._emit_record()
        return did

    def _reap(self):
        now = time.monotonic()
        doomed = []
        with self._cond:
            for r in list(self._queue):
                if r._cancelled or (r.deadline is not None
                                    and now > r.deadline):
                    self._queue.remove(r)
                    doomed.append(r)
            for r in list(self._active):
                if r._cancelled or (r.deadline is not None
                                    and now > r.deadline):
                    self._active.remove(r)
                    doomed.append(r)
        for r in doomed:
            if r._cancelled:
                self._finish(r, None, cancelled=True)
            else:
                telemetry.note("decode_timeout")
                profiler.increment_counter("decode_timeouts")
                self._finish(r, RequestTimeoutError(
                    "request %s deadline passed after %.1f ms "
                    "(%d/%d tokens generated)"
                    % (r.request_id,
                       (tracing.now() - r.t_submit) * 1e3,
                       len(r.generated), r.max_new)))

    def _finish(self, req, error, cancelled=False):
        """Retire one request: reclaim its pages (the counted
        ``kv_evict`` path), account it, complete the future. A
        cancelled request completes WITHOUT an error — its stream just
        ends and ``result()`` returns the tokens generated so far,
        with ``state == "cancelled"`` telling the story."""
        if req.trace_args is not None and req._t_trace is not None:
            tracing.add(
                "decode", "decode", req._t_trace,
                tracing.now() - req._t_trace,
                tid=tracing.track("req %s" % req.trace_args["request_id"]),
                args=dict(req.trace_args,
                          tokens=len(req.generated),
                          outcome=("cancelled" if cancelled
                                   else "ok" if error is None
                                   else type(error).__name__)))
            req._t_trace = None
        if req.pages:
            if self._prefix_on and not cancelled and error is None \
                    and req.params is not None:
                # a clean completion's K/V is written for every
                # position except the LAST generated token's (a step
                # writes its INPUT token) — register the full pages of
                # prompt + generated[:-1] so later prompts continuing
                # this conversation share them
                run = [int(t) for t in req.prompt] \
                    + [int(t) for t in req.generated[:-1]]
                self._pool.prefix_insert(
                    self._namespace(req.params), run, req.pages)
            self._pool.free(req.pages)
            req.pages = []
        # whatever of its prompt was still to be fed goes with the pages
        req.pending = None
        if req.slot is not None:
            # the row of the state arrays goes back as it is: its next
            # tenant's prefill writes it whole
            self._pool.release_row(req.slot)
            req.slot = None
        with self._cond:
            if cancelled:
                self._stats["cancelled"] += 1
            elif error is None:
                self._stats["completed"] += 1
            elif isinstance(error, RequestTimeoutError):
                self._stats["timeouts"] += 1
            elif isinstance(error, ServerOverloadedError):
                self._stats["preempted"] += 1
            else:
                self._stats["errors"] += 1
            self._cond.notify_all()
        req._complete(error, state="cancelled" if cancelled else None)

    def _pick_victim(self, below, exclude=None):
        """The preemption victim under KV-pool pressure: the NEWEST
        member of the LOWEST priority class strictly below ``below``
        among active requests. None when nothing qualifies."""
        with self._cond:
            best = None
            for r in self._active:
                if r is exclude or r.priority >= below:
                    continue
                if best is None or r.priority < best.priority:
                    best = r
                elif r.priority == best.priority:
                    best = r        # later in list = newer
            if best is not None:
                self._active.remove(best)
        return best

    def _preempt(self, victim):
        telemetry.note("decode_preempted")
        profiler.increment_counter("decode_preempted")
        self._finish(victim, ServerOverloadedError(
            "decode: request %s (priority %d) preempted under KV-"
            "pool pressure after %d token(s) — raise "
            "MXNET_KV_POOL_PAGES or lower concurrency"
            % (victim.request_id, victim.priority,
               len(victim.generated))))

    def _admit_one(self):
        with self._cond:
            if self._stopping and not self._drain:
                return False
            if not self._queue or sum(
                    not self._ending(r)
                    for r in self._active) >= self._window:
                return False
            req = self._queue[0]
            ver = self._params    # pinned BEFORE the index lookup —
                                  # a racing swap must not mismatch
                                  # the namespace and the weights
        with tracing.span("decode.admit", request_id=req.request_id,
                          prompt_len=len(req.prompt)) as sp:
            return self._admit(req, ver, sp)

    def _admit(self, req, ver, sp):
        """The head of the queue, under its ``decode.admit`` span
        ``sp``: pages (shared prefix pages first), activation, and then
        the prompt — set pending for the decode step to carry in chunks
        (nothing is launched), or run through the prefill program of its
        rung where the model's form or the pool's layout keeps one."""
        P = len(req.prompt)
        rung = self._seq_ladder.bucket_for(P)
        shared, cached = [], 0
        if self._prefix_on:
            shared, cached = self._pool.prefix_lookup(
                self._namespace(ver), req.prompt)
            with self._cond:
                if shared:
                    self._stats["prefix_hits"] += 1
                    self._stats["prefix_hit_tokens"] += cached
                else:
                    self._stats["prefix_misses"] += 1
            if shared:
                # credited at the SAME point the server's own hit
                # counters increment, so metering's per-tenant credits
                # reconcile exactly with prefix_hit_tokens
                metering.request_prefix(
                    metering.inner_key(self, req.request_id), cached,
                    cached * self._pool.token_bytes)
        sp.set(rung=rung, cached=cached,
               queue_wait_us=round((sp.t0 - req.t_submit) * 1e6, 1))
        # a block model's first block of the answer starts inside the
        # prompt's last (partial) block and is written whole
        first = P // self._block * self._block if self._block else P
        need = self._pool.pages_for(
            max(P + 1, first + self._block)) - len(shared)
        pages = self._pool.alloc(need, owner=self._owner)
        while pages is None:
            victim = self._pick_victim(below=req.priority)
            if victim is None:
                # nothing of ours to evict: ask lower-pool-priority
                # co-tenants to give pages back, then wait — either
                # way the retained prefix refs must come back, or the
                # retry next tick would double-count them
                self._pool.request_preempt(self._owner)
                if shared:
                    self._pool.free(shared)
                return False
            self._preempt(victim)
            pages = self._pool.alloc(need, owner=self._owner)
        with self._cond:
            if not self._queue or self._queue[0] is not req \
                    or req._cancelled:
                # reaped or cancelled while we were allocating
                pages_back = shared + pages
            elif self._state and not self._take_slot_locked(req):
                # every row of the state arrays is held by a request
                # that still steps: wait, like for pages
                pages_back = shared + pages
            else:
                self._queue.popleft()
                req.pages = shared + pages
                req.state = "active"
                req.params = ver
                req.prefix_cached = cached
                self._active.append(req)
                self._stats["admitted"] += 1
                self._stats["queue_wait_s"] += sp.t0 - req.t_submit
                pages_back = None
        if pages_back is not None:
            self._pool.free(pages_back)
            return False
        if shared or self._chunk:
            # no prefill program at all: the prompt — after a prefix
            # hit, its un-cached suffix — feeds through the decode step,
            # ``chunk`` tokens a step on the lanes of the mixed program
            # (one token a step on the row's own lane where the server
            # has none), outputs discarded until the last, which IS the
            # first generated token: the stepwise≡full-forward greedy
            # contract makes the stream token-identical to a prefilled
            # run. Nothing is launched here. A fully-cached page-aligned
            # prompt re-runs only its last token; its write COWs the
            # shared page.
            start = min(cached, P - 1)
            req.pending = deque(int(t) for t in req.prompt[start:])
            req.pending_pos = start
            if self._state:
                # its row of the state arrays is written anew from its
                # first chunk on
                self._pool.note_state_write()
            if req.trace_args is not None:
                tracing.add(
                    "queue", "decode", req._t_trace, sp.t0 - req._t_trace,
                    tid=tracing.track(
                        "req %s" % req.trace_args["request_id"]),
                    args=req.trace_args)
                req._t_trace = sp.t0
            return True
        # run the prefill program at the prompt's rung
        tokens = _np.zeros((1, rung), _np.int32)
        tokens[0, :P] = req.prompt
        pt = _np.zeros((self._max_pages,), _np.int32)
        pt[:len(req.pages)] = req.pages
        slot = ()
        if self._state:
            slot = (_np.int32(req.slot),)
            self._pool.note_state_write()
        with tracing.span("decode.prefill", rung=rung,
                          **({"state_slot": req.slot} if self._state
                             else {})) as pre:
            self._seq = seq = self._seq + 1
            try:
                with self._pool.step_lock:
                    with tracing.span("decode.prefill.launch", seq=seq,
                                      program="prefill",
                                      rung=rung) as launch:
                        out = self._prefill_progs[rung](
                            req.params.tree, tokens, _np.int32(first), pt,
                            *slot, *self._pool.arrays)
                    out = self._adopt_pool(out)
            except Exception as exc:   # noqa: BLE001 — model errors
                self._retire([req], exc)   # belong to the request
                return True
            if metering.enabled():
                # a prefill batch is this one request: the whole
                # program cost (compile-watch cost_analysis) is its
                # share
                cost = compile_watch.last_dispatch(
                    "%s:prefill:s%d" % (self._site, rung))
                if cost is not None:
                    metering.request_flops(
                        metering.inner_key(self, req.request_id),
                        cost["flops"], cost["bytes"])
            if self._block:
                # nothing to read: the prefill emits no token, so its
                # dispatch is all the host does here (an error of the
                # program surfaces where the next step is read back),
                # and the answer's first block opens on the prompt's
                # remainder, positions already unmasked
                self._open_block(req, first, req.prompt[first:])
                with self._cond:
                    self._stats["prefill_steps"] += 1
                    self._stats["prefill_s"] += tracing.now() - pre.t0
                    self._note_launch_locked("prefill", launch)
                return True
            if self._prefix_on:
                # the prefill just wrote K/V for every prompt position:
                # register the full pages so the NEXT same-prefix
                # prompt shares them (the index retains its own
                # reference)
                self._pool.prefix_insert(self._namespace(ver),
                                         req.prompt, req.pages)
            with tracing.span("decode.prefill.read", waits=seq) as read:
                if self._spec:
                    # the first token and the first draft, one read
                    tok, req.draft = (int(t) for t in _np.asarray(out[0]))
                else:
                    tok = int(out[0])
            if self._spec:
                req.drafts.append(-1)
        req._last_emit = pre.t1
        if req.trace_args is not None:
            rtid = tracing.track("req %s" % req.trace_args["request_id"])
            tracing.add("queue", "decode", req._t_trace,
                        pre.t0 - req._t_trace, tid=rtid,
                        args=req.trace_args)
            tracing.add("prefill", "decode", pre.t0, pre.t1 - pre.t0,
                        tid=rtid, args=dict(req.trace_args, rung=rung))
            req._t_trace = pre.t1
        with self._cond:
            self._stats["prefill_steps"] += 1
            self._stats["prefill_s"] += pre.t1 - pre.t0
            self._stats["prefill_read_wait_s"] += read.t1 - read.t0
            self._note_launch_locked("prefill", launch)
            self._stats["tokens_out"] += 1
            self._ttft.append((pre.t1 - req.t_submit) * 1e3)
        req.generated.append(tok)
        req._push(tok)
        if len(req.generated) >= req.max_new or \
                (req.eos_id is not None and tok == req.eos_id):
            self._retire([req], None)
        return True

    def _take_slot_locked(self, req):
        """A row of the state arrays for ``req`` (under ``self._cond``):
        a free one, or the row of a request that needs no further step
        — its last step is dispatched already, and the prefill that
        writes the row runs behind it. False when every row steps on."""
        slot = self._pool.take_row()
        if slot is None:
            for r in self._active:
                if r.slot is not None and self._ending(r):
                    slot, r.slot = r.slot, None
                    break
        req.slot = slot
        return slot is not None

    def _ensure_pages(self, rows):
        """Grow each row's page table to cover its next write
        position, preempting lower-priority active requests under
        pool pressure (the row itself fails if nothing below it can
        be evicted). A write position landing in a still-SHARED page
        (prefix cache) copies it first — copy-on-write. Returns the
        surviving rows."""
        survivors = []
        for r in rows:
            if r.state != "active":
                continue               # preempted earlier in this pass
            failed = False
            while True:
                # (a block model: the last position of the block AFTER
                # the one the next step works on, whose first pass rides
                # with that block's commit — held one block early — or of
                # the block itself where the row ends before the next; a
                # self-drafting one: the furthest its next step can
                # write, two positions from where it starts, which is
                # one or two past the start of a step still unread)
                # (a row whose prompt rides the step in chunks: every
                # position of its next chunk, ``first`` to ``wp``)
                first = wp = r.pending_pos if r.pending \
                    else self._last_block_position(r) if self._block \
                    else self._spec_position(r) + 1 + 2 * r.unread \
                    if self._spec \
                    else len(r.prompt) + len(r.generated) + r.unread - 1
                if r.pending and self._chunks:
                    wp += min(self._chunk, len(r.pending)) - 1
                needed = wp // self._pool.page_size + 1
                while len(r.pages) < needed:
                    pg = self._pool.alloc(1, owner=self._owner)
                    if pg is not None:
                        r.pages.extend(pg)
                        continue
                    victim = self._pick_victim(below=r.priority,
                                               exclude=r)
                    if victim is None:
                        if self._pool.request_preempt(self._owner):
                            # a co-tenant will give pages back: skip
                            # this row's step, it stays active and
                            # retries next tick
                            failed = True
                            break
                        with self._cond:
                            if r in self._active:
                                self._active.remove(r)
                        self._preempt(r)
                        failed = True
                        break
                    self._preempt(victim)
                    if victim in survivors:
                        survivors.remove(victim)
                if failed:
                    break
                shared = next(
                    (i for i in range(first // self._pool.page_size,
                                      needed)
                     if self._pool.ref(r.pages[i]) > 1), None) \
                    if self._prefix_on else None
                if shared is not None:
                    got = self._cow_row(r, shared)
                    if got == "died":
                        failed = True
                        break
                    # (split: the pages behind it may be shared too;
                    # degraded: re-alloc from position 0)
                    continue
                break
            if not failed:
                survivors.append(r)
        # a drain on the way (a degraded split) may have ended a row
        return [r for r in survivors if r.state == "active"]

    def _cow_row(self, r, pidx):
        """Copy-on-write split of ``r``'s still-shared page ``pidx``:
        copy the page body (q8: and its scales) to a fresh private
        page with the ``:cow`` program, drop the writer's reference
        from the shared one, swap the table entry. Visits the
        ``kv_cow`` fault site; a planned raise there degrades the row
        to a PRIVATE re-prefill of everything it has computed so far —
        greedy decode makes the degraded stream token-identical, never
        a wrong token. Returns "ok" | "degraded" | "died"."""
        try:
            fault.inject("kv_cow")
        except fault.InjectedFault:
            # the re-feed starts from what the row HAS generated: read
            # the unread step first, which may be the row's last
            self._read_unread("cow_degraded")
            if r.state != "active":
                return "died"
            with self._cond:
                self._stats["cow_degraded"] += 1
            self._degrade_private(r)
            return "degraded"
        pg = self._pool.alloc(1, owner=self._owner)
        while pg is None:
            victim = self._pick_victim(below=r.priority, exclude=r)
            if victim is None:
                with self._cond:
                    if r in self._active:
                        self._active.remove(r)
                self._preempt(r)
                return "died"
            self._preempt(victim)
            pg = self._pool.alloc(1, owner=self._owner)
        old, new = int(r.pages[pidx]), int(pg[0])
        self._seq = seq = self._seq + 1
        with self._pool.step_lock:
            with tracing.span("decode.cow.launch", seq=seq,
                              program="cow") as launch:
                out = self._cow_prog(*self._pool.arrays,
                                     _np.int32(old), _np.int32(new))
            self._adopt_pool(out)
        self._pool.cow_release(old)
        r.pages[pidx] = new
        with self._cond:
            self._stats["cow_splits"] += 1
            self._note_launch_locked("cow", launch)
        return "ok"

    def _degrade_private(self, r):
        """Fall back to a fully private row: drop every page
        reference (shared pages just decrement — the other holders
        keep them) and queue everything the row has computed so far —
        prompt + generated — through the decode-step program from
        position 0. Pages re-grow privately as the feed advances."""
        if r.pages:
            self._pool.free(r.pages)
            r.pages = []
        r.pending = deque(
            [int(t) for t in r.prompt] + [int(t) for t in r.generated])
        r.pending_pos = 0
        r.prefix_cached = 0

    def _ending(self, r):
        """True for a row that needs no further step: it ends by count
        with the token of the unread step, which is known before that
        token is read. Such a row stays active until its step is read
        and is in no later step; its slot is free for an admission."""
        return not self._block \
            and len(r.generated) + r.unread >= r.max_new

    def _decode_once(self):
        """Dispatch the next decode step over every row that needs
        one, THEN read back the step before it: what the host does
        with a step's tokens runs under the next step. Reading first
        ("draining") is the same path at depth 0, taken when the
        scheduler can see it must, and counted by cause."""
        with self._cond:
            ask, self._drain_ask = self._drain_ask, None
        if ask is not None:
            self._read_unread(ask)
        with self._cond:
            rows = [r for r in self._active if not self._ending(r)]
        if not rows:
            # nothing to plan: an unread step is the last of its rows
            return self._read_unread()
        try:
            fault.inject("serve_decode")
        except fault.InjectedFault:
            # a planned raise/hang at the decode site: count it and
            # keep scheduling — active requests age meanwhile, which
            # is how deadline tests drive the timeout+reclaim path
            with self._cond:
                self._stats["decode_faults"] += 1
            self._read_unread("fault")
            return True
        if len({r.params for r in rows}) > 1:
            # two weight generations cannot share a step, and a step
            # is fed from ONE step before it: depth 0 until one drains
            self._read_unread("versions")
        with tracing.span("decode.pages"):
            rows = self._ensure_pages(rows)
        if not rows:
            self._read_unread()
            return True
        groups = {}
        for r in rows:
            groups.setdefault(r.params, []).append(r)
        for ver in sorted(groups, key=lambda v: v.version):
            self._decode_group(ver, groups[ver])
            if len(groups) > 1:
                self._read_unread("versions")
        return True

    def _decode_group(self, ver, rows):
        D, M = self._window, self._max_pages
        prev = self._unread
        if self._block:
            return self._dispatch_step(
                ver, rows, *self._build_block_step(rows, prev), prev)
        if self._spec:
            return self._dispatch_step(
                ver, rows, *self._build_spec_step(rows, prev), prev)
        with tracing.span("decode.build"):
            tokens = _np.zeros((D,), _np.int32)
            positions = _np.zeros((D,), _np.int32)
            pts = _np.zeros((D, M), _np.int32)
            src = _np.full((D,), -1, _np.int32)
            slots = {} if prev is None else prev.slots
            emits = []
            chunk = None
            decoding = _np.zeros((D,), bool)
            if self._state and self._chunks:
                # a state step's live rows are its first: the rows that
                # decode, then those whose prompt is still pending (FIFO
                # admits in that order anyway; this holds it)
                rows.sort(key=lambda r: bool(r.pending))
            for i, r in enumerate(rows):
                if r.pending and self._chunks:
                    # its prompt rides the step in chunks: the
                    # head-most such row is fed this step's (FIFO, one
                    # request's chunk a step), the others wait; either
                    # way the row's own lane stays out of the step (no
                    # table: the dump page) until its last chunk is in
                    if chunk is None:
                        chunk = self._build_chunk(i, r)
                    emits.append(chunk[0] is r and r.pending is None)
                    continue
                decoding[i] = True
                if r.pending:
                    # prefix-cache suffix feed where no chunk runs: the
                    # next un-cached token runs through the same step
                    # program at its own absolute position. The feed
                    # advances here, at dispatch: nothing it plans from
                    # is computed
                    tokens[i] = r.pending.popleft()
                    positions[i] = r.pending_pos
                    r.pending_pos += 1
                    if not r.pending:
                        r.pending = None
                    emits.append(r.pending is None)
                else:
                    if r.unread:
                        # its input is the unread step's output: taken
                        # on the device, from the slot it had there
                        src[i] = slots[id(r)]
                    else:
                        tokens[i] = r.generated[-1]
                    positions[i] = len(r.prompt) + len(r.generated) \
                        + r.unread - 1
                    emits.append(True)
                pts[i, :len(r.pages)] = r.pages
            # the pages that hold this step's live keys, of the table
            # the step program is compiled for: what a kernel that
            # reads pages where they lie has to stream
            pages_live = int((positions[decoding]
                              // self._pool.page_size + 1).sum())
            feed, said = (tokens, positions), {}
            if chunk is not None:
                fed, n, _array = chunk
                pages_live += (fed.pending_pos - 1) \
                    // self._pool.page_size + 1
                said = {"chunk": n, "chunk_of": fed.request_id}
            if self._state:
                # every row of the state arrays, the live rows' own
                # first: what a row whose prompt is still pending holds
                # (the chunk's lanes alone write the fed one's: a lane of
                # its own at position 0 would write slot 0 of its ring),
                # what another weight generation's rows hold, and what
                # nobody holds, rides behind them, not live
                own = [r.slot for r in rows]
                rest = sorted(set(range(D)) - set(own))
                live = int(decoding.sum())
                feed += (_np.asarray(own + rest, _np.int32),
                         _np.int32(live))
                said = dict(said, state_rows_live=live)
            if self._mesh is not None:
                # what one chip hands the step's all-reduces, from the
                # program's static shapes and the lanes of this step
                lanes = D + (len(chunk[2]) - M - 3 if chunk else 0)
                said = dict(said, mesh=self._shards,
                            exchange_bytes=self._model.exchange_bytes(
                                lanes))
        self._dispatch_step(ver, rows, emits, feed + (pts,), src,
                            pages_live, said, prev, chunk)

    def _build_chunk(self, slot, r):
        """The next chunk of ``r``'s pending tokens, for the mixed step
        in which ``r`` is row ``slot``: ``(r, its tokens, the program's
        chunk array)`` — the lanes' tokens, ``r``'s page-table row, the
        first lane's position, the live lanes and the slot; the array's
        length says which of the mixed programs takes it, the smallest
        that holds what is pending (the largest, while more is). The
        feed advances here, at dispatch: nothing it plans from is
        computed."""
        M = self._max_pages
        C = next((c for c in self._chunks if c >= len(r.pending)),
                 self._chunk)
        n = min(C, len(r.pending))
        array = _np.zeros((C + M + 3,), _np.int32)
        array[:n] = [r.pending.popleft() for _ in range(n)]
        array[C:C + len(r.pages)] = r.pages
        array[C + M:] = r.pending_pos, n, slot
        r.pending_pos += n
        if not r.pending:
            r.pending = None
        return r, n, array

    def _dispatch_step(self, ver, rows, emits, feed, src, pages_live,
                       said, prev, chunk=None):
        """Dispatch the step that was built (``feed``: the host's arrays
        in front of ``prev`` in the program's signature; ``said``: what
        else the ``decode.dispatch`` span carries; ``chunk``: what
        :meth:`_build_chunk` made, which takes the mixed program), then
        read back the step before it."""
        self._seq = seq = self._seq + 1
        lanes = len(chunk[2]) - self._max_pages - 3 if chunk else 0
        prog, carried = (self._chunk_progs[lanes], (chunk[2],)) if chunk \
            else (self._decode_prog, ())
        try:
            with tracing.span("decode.dispatch", seq=seq, program="step",
                              pages_live=pages_live,
                              ahead=int(prev is not None),
                              **said) as launch, self._pool.step_lock:
                toks = self._adopt_pool(prog(
                    ver.tree, *feed,
                    self._no_prev if prev is None else prev.toks, src,
                    *carried, *self._pool.arrays))[0]
        except Exception as exc:       # noqa: BLE001 — model errors
            # belong to the batch's requests, after what the step
            # before computed for them has been handed out
            self._read_unread("error")
            self._retire(rows, exc)
            return
        for r, emit in zip(rows, emits):
            r.unread += emit
        self._unread = _Step(rows, emits, toks, pages_live,
                             prev is not None, seq, launch,
                             chunk and (*chunk[:2], lanes))
        if chunk is not None and self._prefix_on:
            # the prompt's pages that this chunk completed are written
            # (in the device's order) before whatever is dispatched
            # later reads them: the NEXT same-prefix prompt shares them
            # from here on (the index retains its own reference)
            fed = chunk[0]
            self._pool.prefix_insert(
                self._namespace(ver), _np.concatenate(
                    [fed.prompt, _np.asarray(fed.generated, _np.int32)]
                )[:fed.pending_pos], fed.pages)
        if prev is not None:
            self._read(prev)

    # -- a block model's rows ----------------------------------------------
    # The host keeps each row's block AS OF THE LAST STEP READ BACK
    # (``DecodeRequest.blk_*``). The step being built runs while the step
    # before it is unread, and what that step did follows from the same
    # state: with nothing masked it COMMITS the block and runs the first
    # denoising pass of the block after it, so the next step works on
    # that block; with something masked it DENOISES, and stays. Either
    # way the row's state lies in the unread step's output — under a rule
    # that decides on the device how many positions a pass unmasks the
    # host does not know how a pass ends — and the program decides there
    # what the next pass is. Positions and pages are always known.
    def _open_block(self, r, start, held=()):
        """Row ``r``'s state at the opening of the block at ``start``:
        ``held`` (prompt tokens) already unmasked, the rest masked."""
        B = self._block
        n = len(held)
        r.blk_start = int(start)
        r.blk_x = [int(t) for t in held] \
            + [self._model.mask_token_id] * (B - n)
        r.blk_masked = [False] * n + [True] * (B - n)
        r.blk_when = [-1] * n + [None] * (B - n)
        r.blk_pass = 0

    def _next_block(self, r):
        """``(start, known)`` of the block the NEXT step runs for ``r``:
        where it starts, and whether the host knows the block's state (as
        last read) or it lies in the unread step's output."""
        prev = self._unread
        if prev is None or id(r) not in prev.slots:
            return r.blk_start, True
        # the unread step commits a block with nothing masked and leaves
        # the row on the block after it, one denoising pass in
        settled = not any(r.blk_masked)
        return r.blk_start + (self._block if settled else 0), False

    def _last_block_position(self, r):
        """The last position the next step may run for ``r``: that of
        the block after the one it works on, unless the row ends before
        it (a block lies in one page, the next may lie in the next)."""
        start = self._next_block(r)[0] + self._block
        return start + (self._block if start < self._end(r) else 0) - 1

    @staticmethod
    def _end(r):
        """The position ``r`` ends at: no block at or past it is run."""
        return len(r.prompt) + r.max_new

    def _build_block_step(self, rows, prev):
        """The host's arrays of one block step, what its dispatch span
        says, and the slots fed from the unread step."""
        D, M, B = self._window, self._max_pages, self._block
        with tracing.span("decode.build"):
            x = _np.zeros((D, B), _np.int32)
            state = _np.full((D,), -1, _np.int32)
            positions = _np.zeros((D,), _np.int32)
            ends = _np.zeros((D,), _np.int32)
            pts = _np.zeros((D, M), _np.int32)
            src = _np.full((D,), -1, _np.int32)
            slots = {} if prev is None else prev.slots
            for i, r in enumerate(rows):
                positions[i], known = self._next_block(r)
                ends[i] = self._end(r)
                if known:
                    x[i] = r.blk_x
                    state[i] = sum(m << j for j, m
                                   in enumerate(r.blk_masked))
                else:
                    # any state but "no row": the program takes the
                    # unread step's
                    src[i], state[i] = slots[id(r)], (1 << B) - 1
                pts[i, :len(r.pages)] = r.pages
            n = len(rows)
            pages_live = int(((positions[:n] + B - 1)
                              // self._pool.page_size + 1).sum())
            fed = src[:n] >= 0
            committing = _np.logical_and(state[:n] == 0,
                                         _np.logical_not(fed))
            # the rows KNOWN to commit whose next block opens in the
            # same pass, attending everything up to it
            fusing = _np.logical_and(committing,
                                     positions[:n] + B < ends[:n])
        # rows whose kind the program decides from the unread step's
        # output are neither yet: ``undecided``
        said = {"committing": int(committing.sum()),
                "denoising": int(n - committing.sum() - fed.sum()),
                "undecided": int(fed.sum()),
                # the committed keys the host knows the step's rows
                # attend to, in all: a lower bound (an undecided row
                # that commits attends its fresh block's too)
                "keys_live": int(positions[:n].sum()
                                 + (positions[:n] + B)[fusing].sum())}
        return [0] * n, (x, state, positions, ends, pts), src, \
            pages_live, said

    def _read_block(self, step):
        """:meth:`_read` for a block step: every row's block after the
        pass. A denoising pass that leaves nothing masked SETTLES the
        block: its tokens are final (an unmasked token is never masked
        again) and are pushed together, in order, when that pass is read
        back — one step before the pass that caches them. That pass
        (kind 3) commits the block and is the first denoising pass of
        the block after it: the host opens the fresh block and reads what
        the pass unmasked in it, which may settle it at once. A commit
        that ran by itself (kind 2) emits nothing. ``max_new`` and
        ``eos_id`` cut the last block; a request ends when its last
        block settles, uncommitted."""
        D, B = self._window, self._block
        try:
            with tracing.span("decode.readback", waits=step.seq) as back:
                toks, step.toks = _np.asarray(step.toks), None
                out = toks[:D * (B + 2)].reshape(D, B + 2)
                live = [(i, r) for i, r in enumerate(step.rows)
                        if r.state == "active"]
                kinds = [int(out[i, B + 1]) for i, _r in live]
                # a committed-and-denoised row's block was all masked
                unmasked = [
                    [j for j in range(B)
                     if (kind == 3 or r.blk_masked[j])
                     and not out[i, B] >> j & 1]
                    if kind in (1, 3) else []
                    for (i, r), kind in zip(live, kinds)]
                counts = {"tokens_unmasked": sum(map(len, unmasked)),
                          "blocks_committed": sum(k in (2, 3)
                                                  for k in kinds),
                          "blocks_fused": kinds.count(3)}
                model_counts = self._model_counts(toks, D * (B + 2))
                back.set(**counts, **(model_counts or {}))
        except Exception as exc:       # noqa: BLE001 — the step's error
            self._retire(step.rows, exc)
            self._read_unread("error")
            return
        now = back.t1
        with tracing.span("decode.emit", rows=len(step.rows)) as emit:
            self._bill_step(step)
            pushed, finished = [], []
            for (i, r), kind, newly in zip(live, kinds, unmasked):
                if kind in (2, 3):
                    self._open_block(r, r.blk_start + B)
                if kind == 2:
                    continue
                for j in newly:
                    r.blk_when[j] = r.blk_pass
                r.blk_x = [int(t) for t in out[i, :B]]
                r.blk_masked = [bool(out[i, B] >> j & 1)
                                for j in range(B)]
                r.blk_pass += 1
                if any(r.blk_masked):
                    continue
                for j in range(max(len(r.prompt) - r.blk_start, 0), B):
                    r.generated.append(r.blk_x[j])
                    r.unmask_pass.append(r.blk_when[j])
                    r._push(r.blk_x[j])
                    pushed.append(r)
                    if len(r.generated) >= r.max_new or \
                            (r.eos_id is not None
                             and r.blk_x[j] == r.eos_id):
                        finished.append(r)
                        break
            emit.set(emitted=len(pushed))
            with self._cond:
                st, bl = self._stats, self._blocks
                self._note_step_locked(step, model_counts, back)
                # a pass is one row's, whatever it did: a commit that
                # rode with a denoising pass is that denoising pass
                bl["commit_passes"] += kinds.count(2)
                bl["denoise_passes"] += len(kinds) - kinds.count(2)
                bl["fused_commits"] += counts["blocks_fused"]
                bl["tokens_unmasked"] += counts["tokens_unmasked"]
                bl["blocks_committed"] += counts["blocks_committed"]
                for r in pushed:
                    st["tokens_out"] += 1
                    if r._last_emit is not None:
                        self._intervals.append((now - r._last_emit) * 1e3)
                    else:
                        self._ttft.append((now - r.t_submit) * 1e3)
                    r._last_emit = now
                    # the passes the block that just settled took: its
                    # commit rides with the next block's first
                    bl["max_passes_a_block"] = max(
                        bl["max_passes_a_block"], r.blk_pass)
            self._retire(finished, None)

    # -- a self-drafting model's rows ---------------------------------------
    # The host keeps each row AS OF THE LAST STEP READ BACK: its tokens
    # (the last is the next step's ``x``) and its draft. A step still
    # unread starts at the position that follows from them; whether it
    # accepts its draft is decided on the device, so the step dispatched
    # behind it takes tokens, draft AND position from its output, and
    # the host only knows that position to within one.
    def _spec_position(self, r):
        """Where the step after the last one READ starts: the position
        of the row's last confirmed token."""
        return len(r.prompt) + len(r.generated) - 1

    def _build_spec_step(self, rows, prev):
        """The host's arrays of one speculative step, what its dispatch
        span says, and the slots fed from the unread step."""
        D, M = self._window, self._max_pages
        with tracing.span("decode.build"):
            tokens = _np.zeros((D, 2), _np.int32)
            positions = _np.zeros((D,), _np.int32)
            pts = _np.zeros((D, M), _np.int32)
            src = _np.full((D,), -1, _np.int32)
            slots = {} if prev is None else prev.slots
            for i, r in enumerate(rows):
                # the least the row's position can be: one past the
                # start of a step still unread
                positions[i] = self._spec_position(r) + r.unread
                if r.unread:
                    src[i] = slots[id(r)]
                else:
                    tokens[i] = r.generated[-1], r.draft
                pts[i, :len(r.pages)] = r.pages
            n = len(rows)
            pages_live = int(((positions[:n] + 1)
                              // self._pool.page_size + 1).sum())
        # ``keys_live``: the keys in the pool that the step's rows attend
        # to AT THE LEAST (an undecided row may be one further on)
        said = {"keys_live": int(positions[:n].sum()),
                "undecided": int((src[:n] >= 0).sum())}
        return [1] * n, (tokens, positions, pts), src, pages_live, said

    def _read_spec(self, step):
        """:meth:`_read` for a speculative step: a row hands out ``y1``
        and, where its draft was accepted, ``y2``; ``max_new`` and
        ``eos_id`` cut the second. The row's next draft is kept for the
        step after the next one read."""
        D = self._window
        for r in step.rows:
            r.unread -= 1
        try:
            with tracing.span("decode.readback", waits=step.seq) as back:
                toks, step.toks = _np.asarray(step.toks), None
                out = toks[:D * _SPEC_OUT].reshape(D, _SPEC_OUT)
                live = [(i, r) for i, r in enumerate(step.rows)
                        if r.state == "active"]
                accepted = sum(int(out[i, 2]) for i, _r in live)
                model_counts = self._model_counts(toks, D * _SPEC_OUT)
                back.set(accepted=accepted, tokens=len(live) + accepted,
                         **(model_counts or {}))
        except Exception as exc:       # noqa: BLE001 — the step's error
            self._retire(step.rows, exc)
            self._read_unread("error")
            return
        now = back.t1
        with tracing.span("decode.emit", rows=len(step.rows)) as emit:
            self._bill_step(step)
            pushed, finished = [], []
            for i, r in live:
                y1, y2, took, r_draft = (int(v) for v in out[i, :4])
                for tok, against in ((y1, r.draft), (y2, -1))[:1 + took]:
                    r.generated.append(tok)
                    r.drafts.append(against)
                    r._push(tok)
                    pushed.append(r)
                    if len(r.generated) >= r.max_new or \
                            (r.eos_id is not None and tok == r.eos_id):
                        finished.append(r)
                        break
                r.draft = r_draft
            emit.set(emitted=len(pushed))
            with self._cond:
                st, sp = self._stats, self._specs
                self._note_step_locked(step, model_counts, back)
                sp["drafts_verified"] += len(live)
                sp["drafts_accepted"] += accepted
                sp["positions_run"] += len(live) * (self._spec + 1)
                sp["tokens_out"] += len(pushed)
                for r in pushed:
                    st["tokens_out"] += 1
                    if r._last_emit is not None:
                        self._intervals.append((now - r._last_emit) * 1e3)
                    r._last_emit = now
            self._retire(finished, None)

    def _retire(self, rows, error):
        """Take whichever of ``rows`` are still active off the active
        list and finish them: cleanly, or with ``error``."""
        with self._cond:
            rows = [r for r in rows if r in self._active]
            for r in rows:
                self._active.remove(r)
        for r in rows:
            self._finish(r, error)

    def _read_unread(self, cause=None):
        """Read the unread step back now, with nothing dispatched
        behind it: the loop at depth 0. ``cause`` says why the
        scheduler had to (``stats()["decode_drains"]``); None where
        there is no next step to plan. True when a step was read."""
        step, self._unread = self._unread, None
        if step is None:
            return False
        if cause is not None:
            with self._cond:
                self._drains[cause] = self._drains.get(cause, 0) + 1
        self._read(step)
        return True

    def _read(self, step):
        """Read one dispatched step's tokens back and hand them out.
        A row that ended while the step was unread (cancelled, past
        its deadline, preempted, stopped, or its ``eos_id`` in the
        step before) ran one step too many: that output is dropped and
        nothing is pushed after the end. Its row write landed at the
        position AFTER everything it generated, in a page it owned at
        dispatch: beyond the run ``_finish`` registers with the prefix
        index, so no published page ever holds one, and whatever is
        dispatched later into a freed page runs after it in the
        device's order."""
        if self._block:
            return self._read_block(step)
        if self._spec:
            return self._read_spec(step)
        D = self._window
        for r, emits in zip(step.rows, step.emits):
            r.unread -= emits
        try:
            with tracing.span("decode.readback", waits=step.seq) as back:
                # the last reference of this side to the step's device
                # token array goes here, so that freeing it (0.3 ms on
                # the chip) is timed
                toks, step.toks = _np.asarray(step.toks), None
                # what the model counted in this step (its routing)
                # exists only now: it rides this span, not the dispatch
                counts = self._model_counts(toks, D)
                back.set(**(counts or {}))
                if self._mesh is not None and counts:
                    # every chip's own, beside chip 0's under the names
                    self._count_chips(toks[D:])
                if self._state:
                    back.set(state_rows_live=step.launch.args[
                        "state_rows_live"])
        except Exception as exc:       # noqa: BLE001 — the step's error
            self._retire(step.rows, exc)
            # whatever was fed from the failed step fails with it
            self._read_unread("error")
            return
        now = back.t1
        with tracing.span("decode.emit", rows=len(step.rows)) as emit:
            emitting = [
                (i, r) for i, (r, emits)
                in enumerate(zip(step.rows, step.emits))
                if emits and r.state == "active"]
            self._bill_step(step)
            emit.set(emitted=len(emitting))
            finished = []
            with self._cond:
                self._note_step_locked(step, counts, back)
                for i, r in emitting:
                    self._stats["tokens_out"] += 1
                    if r._last_emit is not None:
                        self._intervals.append(
                            (now - r._last_emit) * 1e3)
                    else:
                        # the FIRST token of a row whose prompt rode the
                        # step (in chunks, or a prefix hit's suffix)
                        # lands here, not in a prefill — this is its
                        # time-to-first-token, and the end of the
                        # ``prefill`` phase on its track
                        self._ttft.append((now - r.t_submit) * 1e3)
                        if r.trace_args is not None \
                                and r._t_trace is not None:
                            tracing.add(
                                "prefill", "decode", r._t_trace,
                                now - r._t_trace, tid=tracing.track(
                                    "req %s" % r.trace_args["request_id"]),
                                args=dict(r.trace_args,
                                          fed=len(r.prompt)
                                          - r.prefix_cached))
                            r._t_trace = now
                    r._last_emit = now
            for i, r in emitting:
                tok = int(toks[i])
                r.generated.append(tok)
                r._push(tok)
                if len(r.generated) >= r.max_new or \
                        (r.eos_id is not None and tok == r.eos_id):
                    finished.append(r)
            self._retire(finished, None)

    def _bill_step(self, step):
        """The dispatched step program ran ONE batch, every row of it
        the same number of positions (one; a block; a draft and its
        verification): each request it still serves is billed an equal
        share of the program's cost_analysis FLOPs (while metering is
        on) — by the positions it ran, not by the tokens it was handed:
        a rejected draft cost what an accepted one did, and the tokens
        are ``stats()["tokens_out"]``'s to count."""
        if not metering.enabled():
            return
        fed, n, lanes = step.chunk or (None, 0, 0)
        cost = compile_watch.last_dispatch(
            "%s:step%s" % (self._site, ":chunk:c%d" % lanes if n else ""))
        # (the row whose chunk a mixed step carried ran the chunk's
        # positions, not one)
        ran = [(r, n if r is fed else 1)
               for r in step.rows if r.state == "active"]
        total = sum(k for _r, k in ran)
        if cost is not None and total:
            for r, k in ran:
                metering.request_flops(
                    metering.inner_key(self, r.request_id),
                    cost["flops"] * k / total, cost["bytes"] * k / total)

    def _model_counts(self, toks, first):
        """What the model counted in a step, by name, from the step's
        output behind its ``first`` token values; None for a model that
        counts nothing."""
        if self._counters is None:
            return None
        return dict(zip(self._counters[1],
                        (int(c) for c in toks[first:])))

    def _count_chips(self, flat):
        """One step's counters of every chip (``flat``: a vector a chip,
        chip 0's first) into the chips' own running totals."""
        names = self._counters[1]
        with self._cond:
            for chip, tot in enumerate(self._counted_by_chip):
                self._count_step(dict(zip(names, (
                    int(c) for c in flat[chip * len(names):]))), tot)

    def _note_launch_locked(self, program, launch):
        """One program handed to the device into ``stats()`` (under
        ``self._cond``), the seconds from its launch span's stamps."""
        self._launches[program] += 1
        self._launch_s[program] += launch.t1 - launch.t0

    def _note_step_locked(self, step, counts, back):
        """One step READ BACK into ``stats()`` (under ``self._cond``):
        the step itself and its launch, how long its read-back span
        ``back`` stood waiting for it, whether it was dispatched ahead,
        the pages it had to stream, and the model's own counters."""
        st = self._stats
        self._note_launch_locked("step", step.launch)
        st["readback_wait_s"] += back.t1 - back.t0
        st["decode_steps"] += 1
        st["decode_steps_ahead"] += step.ahead
        st["decode_pages_live"] += step.pages_live
        st["decode_pages_table"] += self._window * self._max_pages
        if step.chunk:
            st["chunk_steps"] += 1
            st["chunk_tokens"] += step.chunk[1]
        if counts is not None:
            self._count_step(counts)

    def _count_step(self, counts, tot=None):
        """One decode step's model counters into the running totals
        (under ``self._cond``): a name that starts with ``max`` keeps
        the largest, the others add up; ``last`` is the step's own."""
        tot = self._counted if tot is None else tot
        tot["steps"] = tot.get("steps", 0) + 1
        for name, value in counts.items():
            tot[name] = max(tot.get(name, 0), value) \
                if name.startswith("max") else tot.get(name, 0) + value
        tot["last"] = counts

    # -- stats & telemetry -------------------------------------------------
    def stats(self):
        """Cumulative decode-serving snapshot: request counts, token
        throughput, time-to-first-token and inter-token latency
        percentiles, prefill-vs-decode step mix, KV-pool occupancy,
        swap/version state — the ``decode`` telemetry record, the
        diagnose Decode table, and the /metrics gauges all render
        this. ``chunk`` is a step's budget of prompt tokens, the widest
        of the mixed programs' lanes ``chunk_sizes`` (0, none: prompts
        run a prefill program), ``chunk_steps`` of ``decode_steps`` carried a
        chunk of a prompt, ``chunk_tokens`` prompt tokens in all (the
        prompt tokens admitted less prefix hits, and a degraded row's
        re-feed), ``prefill_programs`` the prefill programs launched (0
        on a server that chunks). Decode steps are counted when they are READ BACK, one
        pass after their dispatch: ``decode_steps_ahead`` of
        ``decode_steps`` were dispatched while the step before them
        was still unread (the host's share of a token hidden under the
        device's), and ``decode_drains`` counts by cause the steps the
        scheduler read before planning the next (``versions``: two
        weight generations alive; ``swap_weights``, ``warmup``,
        ``stop``; ``fault``: a planned ``serve_decode`` raise;
        ``cow_degraded``; ``error``: a dispatch or read-back raised).
        A token's stamp — ``inter_token_ms``, ``ttft_ms`` — is the
        moment its step was read back. ``launches`` counts the programs
        the scheduler handed to the device (``step``, counted like
        ``decode_steps`` when read back; ``prefill``; ``cow``) and
        ``launch_s`` the seconds inside their launch spans;
        ``readback_wait_s`` is the time inside ``decode.readback``: how
        long the scheduler, its own work done, stood waiting for the
        device (over the elapsed time it is the host's slack: near 0 the
        host paces the loop); ``prefill_read_wait_s`` the same for a
        prefill's first token. ``host`` is read when this is called and
        at no other time: the container's CPU throttling
        (``throttled_s``, ``nr_throttled``; 0 where the cgroup keeps no
        count and has no quota, left out where nothing says) and the
        process's ``involuntary_switches``."""
        elapsed = max(tracing.now() - self._t0, 1e-9)
        with self._cond:
            s = dict(self._stats)
            intervals = list(self._intervals)
            ttft = list(self._ttft)
            depth = len(self._queue)
            active = len(self._active)
            version = self._params.version
            versions = {id(r.params) for r in self._active
                        if r.params is not None}
            versions.add(id(self._params))
            shed_pri = dict(self._shed_by_priority)
            counted = dict(self._counted)
            by_chip = [dict(c) for c in self._counted_by_chip]
            blocks = dict(self._blocks)
            specs = dict(self._specs)
            drains = dict(self._drains)
            launches = dict(self._launches)
            launch_s = dict(self._launch_s)
        steps = s["prefill_steps"] + s["decode_steps"]
        out = {
            "name": getattr(self, "_metrics_label", None)
            or self.name or "default",
            "kind": "decode",
            "requests": s["requests"],
            "completed": s["completed"],
            "cancelled": s["cancelled"],
            "timeouts": s["timeouts"],
            "shed": s["shed"],
            "errors": s["errors"],
            "preempted": s["preempted"],
            "queue_depth": depth,
            "queue_peak": s["queue_peak"],
            "max_queue": self._max_queue,
            "active": active,
            "window": self._window,
            "prefill_steps": s["prefill_steps"],
            "prefill_programs": launches["prefill"],
            "chunk": self._chunk,
            "chunk_sizes": list(self._chunks),
            "chunk_steps": s["chunk_steps"],
            "chunk_tokens": s["chunk_tokens"],
            "decode_steps": s["decode_steps"],
            "decode_steps_ahead": s["decode_steps_ahead"],
            "decode_drains": drains,
            "decode_faults": s["decode_faults"],
            "prefill_fraction": round(s["prefill_steps"] / steps, 4)
            if steps else None,
            "tokens_out": s["tokens_out"],
            "tokens_per_sec": round(s["tokens_out"] / elapsed, 3),
            "admitted": s["admitted"],
            "queue_wait_s": s["queue_wait_s"],
            "prefill_s": s["prefill_s"],
            "launches": launches,
            "launch_s": launch_s,
            "readback_wait_s": s["readback_wait_s"],
            "prefill_read_wait_s": s["prefill_read_wait_s"],
            "host": _host_stats(),
            "decode_pages_live": s["decode_pages_live"],
            "decode_pages_table": s["decode_pages_table"],
            "kv": self._pool.stats(),
            "swaps": s["swaps"],
            "weight_version": version,
            "versions_alive": len(versions),
            "ladder": list(self._seq_ladder.buckets),
        }
        if intervals:
            out["inter_token_ms"] = {
                "mean": round(sum(intervals) / len(intervals), 3),
                "p50": round(telemetry.percentile(intervals, 50), 3),
                "p99": round(telemetry.percentile(intervals, 99), 3),
                "max": round(max(intervals), 3),
            }
        if ttft:
            out["ttft_ms"] = {
                "mean": round(sum(ttft) / len(ttft), 3),
                "p50": round(telemetry.percentile(ttft, 50), 3),
                "p99": round(telemetry.percentile(ttft, 99), 3),
            }
        if self._counters is not None:
            out[self._counters[0]] = counted
        if self._mesh is not None:
            # the chips that share every layer; the model's counters
            # above are CHIP 0's (what a reader divides chip 0's kernel
            # time by), every chip's own beside them
            out["mesh"] = self._shards
            if self._counters is not None:
                out[self._counters[0] + "_by_chip"] = by_chip
        if self._state:
            out["state"] = out["kv"]["state"]
        if self._block:
            out["block"] = blocks
        if self._spec:
            out["spec"] = specs
        if shed_pri:
            out["shed_by_priority"] = {str(k): v for k, v
                                       in sorted(shed_pri.items())}
        lookups = s["prefix_hits"] + s["prefix_misses"]
        out["prefix"] = {
            "enabled": self._prefix_on,
            "owner": self._owner,
            "hits": s["prefix_hits"],
            "misses": s["prefix_misses"],
            "hit_rate": round(s["prefix_hits"] / lookups, 4)
            if lookups else 0.0,
            "hit_tokens": s["prefix_hit_tokens"],
            "bytes_saved": s["prefix_hit_tokens"]
            * self._pool.token_bytes,
            "cow_splits": s["cow_splits"],
            "cow_degraded": s["cow_degraded"],
            "cross_preempts": s["cross_preempts"],
            "pool": self._pool.prefix_stats(),
        }
        return out

    def latency_snapshot(self):
        """Recent inter-token intervals (ms) — the /metrics decode
        histogram source."""
        with self._cond:
            return list(self._intervals)

    def _emit_record(self):
        st = self.stats()
        telemetry.decode_event(st)
        if self._prefix_on:
            px = dict(st["prefix"])
            px["name"] = st["name"]
            kv = st.get("kv") or {}
            if "owners" in kv:
                px["owners"] = kv["owners"]
            telemetry.prefix_cache_event(px)


def _host_stats():
    """What the host did to this process, cumulative: the seconds and
    the number of periods its cgroup was throttled by a CPU quota (v2
    ``throttled_usec``, v1 ``throttled_time``; 0 where the group keeps
    no count and says it has no quota; left out where nothing says) and
    the context switches it did not ask for."""
    out = {"involuntary_switches":
           resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw}

    def read(path):
        try:
            with open(path) as f:
                return f.read()
        except OSError:
            return ""

    for line in read("/proc/self/cgroup").splitlines():
        _, controllers, path = line.split(":", 2)
        if controllers and "cpu" not in controllers.split(","):
            continue
        # under a cgroup namespace the process's own group is the mount
        for sub in (path.lstrip("/"), ""):
            group = os.path.join("/sys/fs/cgroup", controllers, sub)
            stat = dict(row.split() for row in read(
                os.path.join(group, "cpu.stat")).splitlines()
                if len(row.split()) == 2)
            if "throttled_usec" in stat:
                out["throttled_s"] = int(stat["throttled_usec"]) / 1e6
            elif "throttled_time" in stat:
                out["throttled_s"] = int(stat["throttled_time"]) / 1e9
            elif read(os.path.join(group, "cpu.max")).startswith("max") \
                    or read(os.path.join(
                        group, "cpu.cfs_quota_us")).strip() == "-1":
                out["throttled_s"] = 0.0        # no quota to run out of
            else:
                continue
            out["nr_throttled"] = int(stat.get("nr_throttled", 0))
            return out
    return out


def req_deadline(deadline_s):
    """Absolute monotonic deadline from a relative seconds budget
    (None disables; 0 is a real immediate deadline)."""
    return time.monotonic() + deadline_s if deadline_s is not None \
        else None
