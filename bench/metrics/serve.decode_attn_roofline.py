"""Share of the roofline the decode attention kernel reaches: the least
time the chip could take for the attention every step of the traced run
needs at its real ``kv_len`` (``bench/work.py``: K and V at ``kv_len``
positions, from shapes), over the device time of the attention kernel's
calls in the trace.  Prefill runs the same decode step per prompt token,
so its positions count too.

The kernel carries no name into the trace yet: it is the Pallas call
(``tpu_custom_call``) whose operands hold the whole cache of one
sequence, ``[kv_heads, context, head_dim]``."""

from bench import work


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    cfg = run.cfg
    dh = cfg["hidden_size"] // cfg["num_attention_heads"]
    kv, ctx = cfg["num_key_value_heads"], cfg["max_position_embeddings"]
    cache = rf"\[{kv},{ctx},{dh}\]"
    either_order = rf"tpu_custom_call(?s:.*){cache}|{cache}(?s:.*)tpu_custom_call"
    kernel_s = run.trace.ops_matching(either_order)
    if kernel_s <= 0:
        return None
    kvs = [kv for _, kv in run.counters["steps"]]
    for _, tokens in run.counters["prefills"]:
        kvs += range(1, tokens + 1)
    need = sum(
        work.roofline_seconds(*work.decode_attention(cfg, n), run.peaks) for n in kvs
    )
    return 100.0 * need / kernel_s
