"""Share of the positions the indexer scored that it chose (%): 100 while
contexts are under 2048 tokens, 2048 / context beyond. Source: /metrics
``gllm_dsa_positions_total``, the growth of ``what="chosen"`` over
``what="seen"``. Layer: runner."""

from lib import sources


def read(run):
    seen = sources.counter_delta(run, "gllm_dsa_positions_total",
                                 '{what="seen"}')
    chosen = sources.counter_delta(run, "gllm_dsa_positions_total",
                                   '{what="chosen"}')
    if not seen or chosen is None:
        return None
    return 100.0 * chosen / seen
