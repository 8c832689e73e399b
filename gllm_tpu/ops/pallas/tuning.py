"""Per-device block-size tuning for the Pallas kernels.

TPU analogue of the reference's per-device Triton autotune tables
(/root/reference/gllm/layers/moe/fused_moe_triton/configs/, ~150 JSON
files keyed by device name): the attention kernels' block sizes are looked
up by (device kind, kernel) instead of being hard-coded at the call site
(VERDICT r03 missing #4).

Resolution order, most specific wins:
1. a JSON table named by ``GLLM_TPU_TUNE_TABLE`` (operator override),
2. the committed ``tables.json`` next to this module (written by
   ``benchmarks/kernel_tune.py --write`` after an on-chip sweep),
3. the BUILTIN defaults (128/256: untuned, known to compile).

Table shape: {device_tag: {kernel: {param: value}}}; ``default`` applies
to every device. device_tag is ``jax.devices()[0].device_kind`` lowercased
with spaces collapsed (e.g. ``tpu_v5_lite``).
"""

from __future__ import annotations

import functools
import json
import logging
import os

logger = logging.getLogger(__name__)

BUILTIN = {
    "default": {
        "ragged": {"q_block": 128, "kv_block": 256},
        # the ragged kernel under ONE KV head (the MLA latent cache): every
        # query head shares the key block, so a q block is q_block x heads
        # rows of MXU work over head_dim lanes, and its windows, float32
        # accumulator and score tile grow with heads x lanes, not with
        # q_block alone. ``q_rows`` bounds the rows; ``ragged_blocks``
        # turns it into the q block of a geometry
        "ragged_mqa": {"q_rows": 1024, "kv_block": 256},
        # the decode kernel under one KV head: a block's bytes a token are
        # one latent row, not heads x (K + V)
        "decode_mqa": {"kv_block": 256},
        # ... under a selection's mask (a selected-attention layer's
        # decoding rows, models/deepseek.py): 128 query heads where
        # ``decode_mqa`` was swept at 64, so the flash state and the
        # score tile of a sequence are twice as large
        "decode_mqa_chosen": {},
        "decode": {"kv_block": 256},
        # f32-score-tile VMEM budget for effective_q_block(); per-device
        # entries are HAND-maintained from kernel_tune.py --vmem-probe's
        # informational output (never auto-written — see the probe's
        # comment on why the score tile is a poor proxy)
        "vmem": {"tile_limit_mb": 6.0},
    },
}

_TABLES_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "tables.json")


def _merge(dst: dict, src: dict) -> None:
    for dev, kernels in src.items():
        d = dst.setdefault(dev, {})
        for kern, params in kernels.items():
            d.setdefault(kern, {}).update(params)


@functools.lru_cache()
def _table() -> dict:
    t = {dev: {k: dict(p) for k, p in kernels.items()}
         for dev, kernels in BUILTIN.items()}
    for path in (_TABLES_PATH, os.environ.get("GLLM_TPU_TUNE_TABLE")):
        if path and os.path.exists(path):
            try:
                with open(path) as f:
                    _merge(t, json.load(f))
            except (OSError, ValueError) as e:
                logger.warning("ignoring tuning table %s: %s", path, e)
    return t


@functools.lru_cache()
def device_tag() -> str:
    import jax
    return "_".join(jax.devices()[0].device_kind.lower().split())


def get(kernel: str) -> dict:
    """Tuned params for ``kernel`` on the current device (device-specific
    entries layered over ``default``). ``comment`` entries are provenance
    annotations (which sweep artifact produced the value) — stripped here
    so they never reach kernel kwargs."""
    t = _table()
    out = dict(t.get("default", {}).get(kernel, {}))
    out.update(t.get(device_tag(), {}).get(kernel, {}))
    out.pop("comment", None)
    return out


def _at(kernel: str, num_q_heads, num_kv_heads: int) -> dict:
    """What a geometry's own entry lays over ``kernel``'s: one swept at
    ``<kernel>@<query heads>x<kv heads>`` (128 x 8 is sixteen query heads
    a KV head where the table's pair was swept at four). A geometry
    without an entry keeps the table's pair."""
    if not num_q_heads:
        return {}
    return get(f"{kernel}@{num_q_heads}x{num_kv_heads}")


def ragged_blocks(num_q_heads: int, num_kv_heads: int) -> dict:
    """{"q_block", "kv_block"} of the ragged kernel at a geometry. With
    several KV heads the table's pair as swept (``ragged``). Under one KV
    head (MLA: 64 or 128 query heads over a latent row of 640 lanes) the
    q block is what keeps ``q_rows`` rows in VMEM: a pair swept at 8 KV
    heads of 128 (512 x 128, the ``ragged`` entry until PR 38) is refused
    there by Mosaic (128.29 MB of VMEM at 64 heads x 640 lanes;
    tests/test_tpu_compile.py pins it)."""
    if num_kv_heads != 1:
        out = get("ragged")
        out.update(_at("ragged", num_q_heads, num_kv_heads))
        return out
    cfg = get("ragged_mqa")
    return {"q_block": max(8, int(cfg["q_rows"]) // num_q_heads // 8 * 8),
            "kv_block": int(cfg["kv_block"])}


def decode_blocks(num_kv_heads: int, chosen: bool = False,
                  num_q_heads: int = 0) -> dict:
    """{"kv_block", "group"} of the decode kernel: the ``decode`` entry,
    with what ``decode_mqa`` says laid over it under one KV head, and
    over that what ``decode_mqa_chosen`` says for the call that takes a
    selection's mask (``chosen``); under several KV heads a geometry's
    own entry (``_at``)."""
    out = get("decode")
    if num_kv_heads == 1:
        out.update(get("decode_mqa"))
        if chosen:
            out.update(get("decode_mqa_chosen"))
    else:
        out.update(_at("decode", num_q_heads, num_kv_heads))
    return out
