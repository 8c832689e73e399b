"""Device-side batch descriptor.

TPU-native analogue of the reference InputData
(/root/reference/gllm/input_data.py:13-802): per-step batch metadata laid out
in flat padded arrays with *static bucketed shapes*, so each (token-bucket,
seq-bucket, max-q-len) combination maps to exactly one compiled program —
the jit-compilation-cache counterpart of the reference's persistent device
buffers + CUDA-graph signature discipline.

The host-side builder lives in gllm_tpu/runner/prepare.py; this module
defines the structure the jit'd step function consumes, and the packed
form in which a host-built batch crosses to the device (``pack`` on the
host, ``unpack`` as the first lines of every step program).
"""

from __future__ import annotations

import math
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from gllm_tpu.ops.attention import AttentionMetadata
from gllm_tpu.ops.sampling import SamplingMetadata


class StepBatch(NamedTuple):
    # Dead-row convention (shared by bucket padding, fused-block
    # active_until masking, and persistent-slot HOLE rows): position 0,
    # slot 0 — KV writes land in the dummy page and the sampled token is
    # discarded host-side, so a dead row costs one attention row and
    # nothing else. Persistent-slot decode batching leans on this to keep
    # a chain's shape signature alive across sequence finishes.
    token_ids: jnp.ndarray       # [T] int32, padded with 0
    positions: jnp.ndarray       # [T] int32 (absolute position in sequence)
    slot_mapping: jnp.ndarray    # [T] int32 flat KV slots (padding → dummy)
    logits_indices: jnp.ndarray  # [S] int32 index of last token per seq in
                                 # the token buffer (padded rows repeat 0)
    attn: AttentionMetadata
    sampling: SamplingMetadata
    # Multimodal extras (VL models only; None keeps text-only programs
    # unchanged — reference model_runner.py:663-1406 MM pipeline):
    mrope_positions: Optional[jnp.ndarray] = None  # [3, T] int32
    mm_embeds: Optional[jnp.ndarray] = None        # [T, H] visual rows
    mm_mask: Optional[jnp.ndarray] = None          # [T] bool (row is visual)
    # Hybrid (GDN) extras: per-seq state slot in the SSM pools (reference
    # sequence.ssm_state_slot → InputData._cal_ssm_metadata); padded rows
    # point at the dummy slot 0.
    ssm_slots: Optional[jnp.ndarray] = None        # [S] int32
    # Prompt-logprob targets: token at position+1 for every prefill row
    # (0 where unavailable); present only when a seq requested
    # prompt_logprobs.
    plp_targets: Optional[jnp.ndarray] = None      # [T] int32
    # Speculative decoding (prompt-lookup drafts, verified in-step):
    # per-seq row indices of the verify rows (padded rows repeat the
    # seq's first row) and the drafts (-1 pad never matches an argmax,
    # stopping acceptance).
    spec_rows: Optional[jnp.ndarray] = None        # [S, k+1] int32
    spec_drafts: Optional[jnp.ndarray] = None      # [S, k] int32


# ---- the packed form ---------------------------------------------------------
#
# A host-built StepBatch is a dozen and more small arrays, and each array
# handed to jax is a crossing of its own (a transfer per leaf, ~0.15 ms
# each on a v5e host while 32 handler threads wait for the interpreter:
# PERF.md, PR 25). So every field but two rides ONE int32 buffer, and a
# static layout says where each lies; the step programs slice it apart
# again before anything else, so nothing below the unpack sees the
# difference. The layout follows from the shapes and from which optional
# fields are present: what the pytree's structure said before, so a
# cell compiles as many programs as it did.

class PackedBatch(NamedTuple):
    """What crosses to the device for one dispatch.

    ``token_ids`` stays a leaf of its own: chained and re-formed steps
    replace or scatter it with the previous step's on-device tokens and
    must run the same program as an unchained step of the same shape.
    ``mm_embeds`` ([T, H] float32, only on steps with visual rows) is
    too large to be worth a copy into the buffer."""
    token_ids: jnp.ndarray                       # [T] int32
    packed: jnp.ndarray                          # [layout.size] int32
    mm_embeds: Optional[jnp.ndarray] = None      # [T, H] float32


class BatchLayout(NamedTuple):
    """Static (hashable) description of a packed buffer, a jit argument
    of every step program: per field its name, offset in int32 words,
    shape and dtype name; and the buffer's length."""
    fields: Tuple[Tuple[str, int, Tuple[int, ...], str], ...]
    size: int

    def has(self, name: str) -> bool:
        return any(f[0] == name for f in self.fields)


# leaves of PackedBatch (never in the buffer) / made inside the program
_OWN_LEAVES = ("token_ids", "mm_embeds")
_NESTED = {"attn": AttentionMetadata, "sampling": SamplingMetadata}
_EXTRA = "x."           # prefix of the fields that are not StepBatch's
# Every field starts on a lane-tile boundary of the 1-D int32 buffer, so
# a program's slices stay aligned copies; at most 0.5 KB per field.
_ALIGN = 128


# (name in the layout, field of StepBatch, field of the tuple nested there)
_PACKED = tuple(
    (f"{name}.{sub}", name, sub) if sub else (name, name, None)
    for name in StepBatch._fields if name not in _OWN_LEAVES
    for sub in (_NESTED[name]._fields if name in _NESTED else (None,))
    if sub != "step_key")


def _named_leaves(batch: StepBatch):
    for name, field, sub in _PACKED:
        value = getattr(batch, field)
        yield name, getattr(value, sub) if sub else value


def pack(batch: StepBatch, step: Sequence[int],
         **extra) -> Tuple[PackedBatch, BatchLayout]:
    """Host side: every numpy field of ``batch`` but ``token_ids`` and
    ``mm_embeds`` into one int32 buffer (float32 and uint32 as their bit
    patterns, bool as 0/1), with the integers the step's PRNG key is
    folded from (``step``: the dispatch's ordinal, then the dp replica
    where there is one) and whatever else a path sends along (``extra``:
    the fused blocks' ``active_until``, the speculation block's carry
    seeds; a None is left out like an absent optional field)."""
    named = [(n, np.asarray(v)) for n, v in _named_leaves(batch)
             if v is not None]
    named.append(("step", np.asarray(step, np.uint32).reshape(-1)))
    named += [(_EXTRA + n, np.asarray(v)) for n, v in extra.items()
              if v is not None]
    fields, off = [], 0
    for name, v in named:
        fields.append((name, off, v.shape, v.dtype.name))
        off += -(-v.size // _ALIGN) * _ALIGN
    layout = BatchLayout(tuple(fields), off)
    buf = np.zeros(off, np.int32)
    for (_, start, _, dtype), (_, v) in zip(fields, named):
        dst = buf[start:start + v.size]
        if dtype == "bool":
            dst[:] = v.reshape(-1)
        else:
            assert v.dtype.itemsize == 4, (dtype, "is not a 4-byte type")
            dst.view(v.dtype)[:] = v.reshape(-1)
    return PackedBatch(batch.token_ids, buf, batch.mm_embeds), layout


def unpack(packed: PackedBatch, layout: BatchLayout,
           rng_key=None) -> Tuple[StepBatch, Dict[str, jnp.ndarray]]:
    """Device side, the first lines of every step program: static slices
    of the buffer back into the StepBatch the bodies read, plus the
    fields ``pack`` was given beside it (``step`` among them).

    With ``rng_key`` the step's PRNG key is made here, inside the
    program: ``fold_in`` of each integer of ``step`` in turn. fold_in
    folds an integer in as data, traced or not, so the key is bit for bit
    the one the host used to compute with a program and a transfer of
    its own per step; a program that never samples (all_greedy, a
    pipeline stage before the last) compiles it away or passes None."""
    vals = {}
    for name, start, shape, dtype in layout.fields:
        x = packed.packed[start:start + math.prod(shape)].reshape(shape)
        if dtype == "bool":
            x = x != 0
        elif dtype != "int32":
            x = jax.lax.bitcast_convert_type(x, jnp.dtype(dtype))
        vals[name] = x
    key = rng_key
    if key is not None:
        for i in range(vals["step"].shape[0]):
            key = jax.random.fold_in(key, vals["step"][i])
    nested = {
        name: cls(**{sub: vals.get(f"{name}.{sub}")
                     for sub in cls._fields if sub != "step_key"},
                  **({"step_key": key} if name == "sampling" else {}))
        for name, cls in _NESTED.items()}
    batch = StepBatch(
        token_ids=packed.token_ids, mm_embeds=packed.mm_embeds, **nested,
        **{name: vals.get(name) for name in StepBatch._fields
           if name not in _OWN_LEAVES and name not in _NESTED})
    extra = {n[len(_EXTRA):]: v for n, v in vals.items()
             if n.startswith(_EXTRA)}
    extra["step"] = vals["step"]
    return batch, extra
