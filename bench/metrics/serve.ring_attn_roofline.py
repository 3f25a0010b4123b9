"""Share of the roofline decode attention on the sliding layers' rings
reaches: the least time the chip could take for every ``decode_attn_ring``
call of the traced run (``bench/work_mellum2.py``: K and V of the
min(kv_len, window) positions in the ring, q, the output and the token's
written row, from shapes, at each token pass's own ``kv_len``), over the
device time of the ops named ``decode_attn_ring``.  Each token pass,
decode step or prompt token, calls it once a sliding layer; the j-th
token of a prompt (from 1) runs at ``kv_len`` = shared context + j."""

from bench import work, work_mellum2

DECODE_ATTN_RING = r"^%?decode_attn_ring(\.\d+)? = "


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    kernel_s = run.trace.ops_matching(DECODE_ATTN_RING)
    if kernel_s <= 0:
        return None
    c = run.counters
    kvs = [kv for _, kv in c["steps"]]
    for _, tokens in c["prefills"]:
        kvs += range(c["prefix_tokens"] + 1, c["prefix_tokens"] + tokens + 1)
    layers = work_mellum2.ring_layers(run.cfg)
    need = sum(
        work.roofline_seconds(*work_mellum2.ring_attention(run.cfg, kv), run.peaks)
        for kv in kvs
    )
    return 100.0 * layers * need / kernel_s
