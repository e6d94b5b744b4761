"""Operations and bytes of the program's named Pallas kernels, from the
shapes of a call alone (``benchmark/flops.py`` does the same for whole
programs). A kernel's event in a profile is named by its HLO line,
``%mx_<kernel>.bh<BH>.q<Tq>.k<Tk>.d<D>.<dtype>.<n> = ...``: the name the
program gave the ``pallas_call``, with the call's static shapes. Under
differentiation JAX puts the transformations in front
(``%transpose_jvp_mx_flash_bwd_dq.bh32...``), so a pattern allows a
prefix of word characters."""
from __future__ import annotations

import re

CALL = re.compile(
    r"^%\w*?mx_\w+\.bh(?P<bh>\d+)\.q(?P<q>\d+)\.k(?P<k>\d+)\.d(?P<d>\d+)\.")


def pattern(kernel):
    """Matches the operation events of one kernel (``flash_decode`` does
    not match ``flash_decode_q8``)."""
    return r"^%%\w*?mx_%s\.bh\d" % re.escape(kernel)


def shapes(event_name):
    """``{bh, q, k, d}`` of one call from its event's name; None when
    the name is not a kernel's."""
    m = CALL.match(event_name)
    return m and {key: int(value) for key, value in m.groupdict().items()}


def causal_attention_flops(bh, q, k, d, v=None):
    """Floating-point operations of causal attention over ``bh`` heads:
    the two products, scores (queries and keys ``d`` wide) and weighted
    values (``v`` wide; ``d`` where not given), over the key positions a
    query may see. Query ``i`` of ``q`` sees the first ``k - q + i + 1``
    of ``k`` keys; a multiply-accumulate is two operations. Where a
    kernel is handed heads zero-padded to a wider tile, ``d`` and ``v``
    are the widths the model has, not the call's: a product with a zero
    is no operation the algorithm needs."""
    visible = q * (k - q) + q * (q + 1) // 2
    return 2 * bh * visible * (d + (d if v is None else v))


def paged_decode_bytes(n_layers, d_model, pages, page_size, kv_bytes=4):
    """Bytes the paged decode-attention kernels of one step read: the
    whole K and V pages that hold the step's live keys, in every layer
    (``pages`` of ``page_size`` tokens: a page is read whole, its last
    rows live or not). The queries and the outputs, one position a row,
    are left out."""
    return 2 * n_layers * pages * page_size * d_model * kv_bytes
