"""The dense decoder's greedy streams, held to recorded tokens.

A change to how ``models/dense.py`` lays out or orders its arithmetic for
the compiler (PR 35: a barrier between the q / k / v dots and their
reshapes to heads) must leave every served token where it was. The
streams below were recorded from the tree BEFORE that change (commit
7ef7675) with this file's own recorder:

    JAX_PLATFORMS=cpu PYTHONPATH=<tree> \
        python tests/test_dense_token_identity.py

Two small configurations cover both branches of ``_attention``: Qwen3's
per-head q / k norm, and Qwen2's q / k / v biases (drawn, not the dummy
recipe's zeros), each in the served bfloat16 and in float32. Prompts
longer than the 32-token prefill budget are chunked, and four sequences
decode together, so the recorded steps are prefill, mixed and decode ones.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gllm_tpu.config import CacheConfig, EngineConfig, SchedulerConfig
from gllm_tpu.models import dense
from gllm_tpu.models.config import ModelConfig
from gllm_tpu.sampling_params import SamplingParams

SMALL = dict(vocab_size=512, hidden_size=64, num_layers=3, num_heads=4,
             num_kv_heads=2, head_dim=16, intermediate_size=112,
             max_position=256)
CONFIGS = {
    "qwen3_qk_norm": ModelConfig(architecture="Qwen3ForCausalLM",
                                 qk_norm=True, **SMALL),
    "qwen2_bias": ModelConfig(architecture="Qwen2ForCausalLM",
                              attention_bias=True, **SMALL),
}

# one list a prompt; bfloat16 and float32 recorded the same streams
RECORDED = {
    "qwen3_qk_norm": [
        [458, 463, 383, 374, 220, 199, 458, 300, 387, 484, 135, 197, 291,
         413, 219, 445, 486, 329, 97, 201],
        [94, 193, 307, 465, 184, 423, 90, 362, 94, 374, 357, 38, 191, 219,
         225, 152, 462, 165, 482, 486],
        [111, 277, 500, 350, 372, 282, 118, 130, 5, 0, 456, 193, 307, 399,
         204, 425, 350, 365, 299, 54],
        [206, 277, 500, 204, 425, 97, 158, 2, 209, 111, 277, 500, 204, 425,
         97, 153, 484, 135, 228, 376],
    ],
    "qwen2_bias": [
        [140, 204, 419, 483, 84, 478, 21, 204, 419, 275, 185, 458, 329, 97,
         334, 419, 275, 333, 223, 204],
        [52, 419, 275, 483, 419, 361, 385, 157, 315, 84, 365, 170, 380,
         193, 193, 193, 193, 193, 193, 292],
        [486, 275, 380, 193, 292, 292, 292, 292, 292, 292, 292, 292, 292,
         292, 292, 292, 292, 292, 292, 292],
        [206, 429, 483, 419, 275, 51, 369, 429, 483, 419, 275, 51, 369,
         429, 483, 419, 361, 46, 383, 204],
    ],
}


def _params(cfg: ModelConfig, dtype):
    params = dense.init_params(cfg, seed=35, dtype=dtype)
    if cfg.attention_bias:
        keys = jax.random.split(jax.random.key(351), 3)
        for key, name in zip(keys, ("q_bias", "k_bias", "v_bias")):
            shape = params["layers"][name].shape
            params["layers"][name] = (
                0.5 * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)
    return params


def streams(name: str, dtype: str):
    from gllm_tpu.engine.llm import LLM
    cfg = CONFIGS[name]
    config = EngineConfig(
        load_format="dummy", dtype=dtype, max_model_len=128, max_num_seqs=4,
        scheduler=SchedulerConfig(max_prefill_tokens=32, max_decode_seqs=4),
        cache=CacheConfig(page_size=4, num_pages=96))
    llm = LLM(config=config, model_cfg=cfg,
              params=_params(cfg, jnp.dtype(dtype)))
    rng = np.random.default_rng(35)
    prompts = [rng.integers(1, 500, size=n).tolist() for n in (9, 23, 41, 70)]
    greedy = SamplingParams(temperature=0.0, max_tokens=20, ignore_eos=True)
    return [list(map(int, o.output_token_ids))
            for o in llm.generate(prompt_token_ids=prompts,
                                  sampling_params=greedy)]


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("name", list(RECORDED))
def test_greedy_streams_are_the_recorded_ones(name, dtype):
    assert streams(name, dtype) == RECORDED[name]


if __name__ == "__main__":
    import gllm_tpu
    print("# recorded from", gllm_tpu.__file__)
    for name in RECORDED:
        for dtype in ("bfloat16", "float32"):
            print(f"{name} {dtype}:")
            for s in streams(name, dtype):
                print(f"    {s},")
