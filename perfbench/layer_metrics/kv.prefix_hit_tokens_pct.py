"""Prompt tokens served from cached pages over prompt tokens asked (%):
the growth of ``gllm_prefix_cache_hit_tokens_total`` over that of
``gllm_prefix_cache_query_tokens_total`` (``PrefixMemoryManager.
match_prefix``, once a request's admission). In the document cell every
request is its caller's document and a fresh question: the document's
whole pages are hits, so it reads 12288 / (12288 + 320) = 97 at the mix's
mean lengths; a document that was evicted and prefilled again shows here
first. Source: /metrics. Layer: KV manager."""

from lib import sources


def read(run):
    asked = sources.counter_delta(run,
                                  "gllm_prefix_cache_query_tokens_total")
    hit = sources.counter_delta(run, "gllm_prefix_cache_hit_tokens_total")
    if not asked or hit is None:
        return None
    return 100.0 * hit / asked
