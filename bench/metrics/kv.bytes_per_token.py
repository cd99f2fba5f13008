"""KV store: bytes of cache the KV manager accounts per offloaded token
(B): ``kv.offload_bytes`` over ``kv.offload_tokens``, from the program's
counters. For a latent-attention model, the latent and the shared roped
key of every layer. A program that does not count offloads reads as
nothing."""
from harness import spans


def read(run):
    tokens = spans.calls("kv.offload_tokens")
    return spans.calls("kv.offload_bytes") / tokens if tokens else None
