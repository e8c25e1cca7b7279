"""Self-speculative decoding from nested GETA subnets.

GETA's joint pruning+quantization training hands serving a *family* of
compression points of one model with shared quantizer scales:
`core.subnet.prepare_serving` resolves quantizers *before* slicing, so an
aggressive subnet (pruned s50 + packed b2/b4) and the b8 target are
mutually consistent by construction. This module turns that artifact
family into a decode-latency multiplier: the subnet drafts k tokens
through the packed GEMM + flash-decode kernels, the target scores all
k+1 positions in one chunked pass (`LM.verify_chunk` — the same
GEMM-shaping win one-shot prefill gets at admission), and a
leading-match rule commits the *target's* argmaxes. Greedy speculative
decode is therefore token-identical to the target-only engine no matter
how bad the draft is — a weak draft costs speed, never tokens — which is
the hard oracle `tests/test_speculative.py` and the CI smoke pin.

Dual-arena bookkeeping: draft and target each own a KV arena shaped by
their own SlimPlan (the draft's holds surviving heads only), sharing slot
indices and per-slot positions. A speculative step writes rows
[pos, pos+k] in both; rejection zeroes every row beyond the accepted
prefix in both (`rollback_rows`). The zero-rollback is exact because full
(window == 0) arenas keep all rows beyond the written prefix at their
zero init — an invariant admission preserves (a prefill row is built in a
fresh zeroed cache and inserted whole) and the rollback property tests
assert bitwise. Ring (windowed) arenas are gated out: a wrap overwrites
pre-wrap history that a rejection could never restore. See DESIGN.md
§4.10.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.core.subnet import prepare_serving, resolve_keep_masks
from repro.models.transformer import LM


@dataclasses.dataclass
class DraftModel:
    """A servable draft subnet: its own (sliced) LM plus the resolved
    (params, qparams) pair. The engine keeps a second KV arena shaped by
    `lm`'s SlimPlan for it."""
    lm: LM
    params: dict
    qparams: Optional[dict]
    meta: dict


def build_draft(arch: str, smoke: bool = True, checkpoint: Optional[dict]
                = None, *, sparsity: float = 0.5, bits: float = 2.0,
                packed: bool = True, seed: int = 0) -> DraftModel:
    """Construct the draft subnet from the target's checkpoint params.

    `checkpoint` is the *same* param dict the target serves from (pre
    `prepare_serving`) — sharing it is what makes the draft
    well-calibrated: quantizers init on the identical tensors, and on a
    GETA-trained checkpoint (pruned groups hard-zeroed by QASSO cooldown)
    the sliced subnet is numerically the target itself at its surviving
    widths. `sparsity=0` keeps all units (a packed-only draft)."""
    cfg = get_arch(arch, smoke=smoke)
    lm = LM(cfg)
    if checkpoint is None:
        checkpoint, _ = lm.init(jax.random.PRNGKey(seed))
    params, qparams, meta = prepare_serving(
        lm, checkpoint, compressed=True, packed=packed, bits_init=bits,
        prune_sparsity=(sparsity if sparsity > 0 else None))
    meta.setdefault("sparsity", 0.0)
    meta["draft_bits"] = bits
    return DraftModel(lm=lm, params=params, qparams=qparams, meta=meta)


def pow2_floor(k: int) -> int:
    """Largest power of two <= k (0 for k < 1) — the draft-window
    quantizer that keeps the engine's compiled spec-step set bounded."""
    k = int(k)
    return 0 if k < 1 else 1 << (k.bit_length() - 1)


def reachable_spec_ks(draft_k: int, max_seq: int) -> set[int]:
    """Every draft-window length `Engine._spec_round` can dispatch:
    k_eff = pow2_floor(min(draft_k, remaining - 1)) enumerated over every
    possible remaining-budget value in [1, max_seq]. Brute force on
    purpose — the static compile-set audit (repro.analysis) diffs this
    against the warmup contract (`Engine._spec_ks`), so it must be an
    independent derivation."""
    return {pow2_floor(min(int(draft_k), rem - 1))
            for rem in range(1, int(max_seq) + 1)}


def rollback_rows(caches: dict, lo, hi) -> dict:
    """Zero arena rows s in [lo[b], hi[b]] for every slot b.

    Cache leaves are (n_blocks, slots, S, ...) — attention K/V as merged
    (KVh * dh) rows, which zeroing never needs per head: axis 1 is the
    slot, axis 2 the sequence row. Zeroing (not just abandoning)
    rejected rows restores the full-arena invariant that everything
    beyond the written prefix equals the zero init — the next write at
    those positions lands on the same bits a never-drafted engine would
    see, and the decode mask (`valid = s <= pos`) never reads them in
    between."""
    lo = jnp.asarray(lo, jnp.int32)
    hi = jnp.asarray(hi, jnp.int32)

    def zap(c):
        s = jnp.arange(c.shape[2])
        stale = (s[None, :] >= lo[:, None]) & (s[None, :] <= hi[:, None])
        m = stale.reshape((1,) + stale.shape + (1,) * (c.ndim - 3))
        return jnp.where(m, jnp.zeros((), c.dtype), c)

    return jax.tree_util.tree_map(zap, caches)


def make_spec_step(target_lm: LM, draft_lm: LM):
    """Build the fused speculative step (jit it with k static).

    One call runs: a k+1-step draft scan (the extra step writes the k-th
    proposal's own K/V row, needed when every proposal is accepted; its
    emitted token is discarded) -> one chunked target verify over
    (last_committed, d_1..d_k) -> leading-match acceptance -> zero
    rollback of rows beyond the accepted prefix in *both* arenas.

    Returns (target argmaxes (B, k+1), n_commit (B,), target caches,
    draft caches). Committed tokens are always the target's argmaxes —
    token identity with a target-only engine is structural; the draft
    only sets how many commit per step (n_commit = 1 + accepted run; the
    +1 is the target's free token). k = 0 degenerates to a plain
    one-token verify whose draft scan still runs once, keeping the draft
    arena in sync through the same code path."""

    def spec_step(tparams, tqparams, dparams, dqparams,
                  tcaches, dcaches, tok, pos, k):
        def draft_body(carry, _):
            dc, t, p = carry
            logits, dc = draft_lm.decode_step(dparams, dqparams, dc, t, p)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return (dc, nxt[:, None], p + 1), nxt

        (dcaches, _, _), drafted = jax.lax.scan(
            draft_body, (dcaches, tok, pos), None, length=k + 1)
        proposals = jnp.moveaxis(drafted, 0, 1)[:, :k]       # (B, k)
        chunk = jnp.concatenate([tok, proposals], axis=1)    # (B, k+1)
        logits, tcaches = target_lm.verify_chunk(
            tparams, tqparams, tcaches, chunk, pos)
        tgt = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, k+1)
        acc = jnp.cumprod((proposals == tgt[:, :k]).astype(jnp.int32),
                          axis=1)
        n_commit = 1 + jnp.sum(acc, axis=1)                  # in [1, k+1]
        tcaches = rollback_rows(tcaches, pos + n_commit, pos + k)
        dcaches = rollback_rows(dcaches, pos + n_commit, pos + k)
        return tgt, n_commit, tcaches, dcaches

    return spec_step


def build_checkpoint_engines(arch: str, smoke: bool = True, *,
                             sparsity: float = 0.5, draft_bits: float = 8.0,
                             draft_k: int = 4, max_slots: int = 4,
                             max_seq: int = 64, seed: int = 0):
    """Target + draft pair as a trained GETA checkpoint would serve them.

    QASSO's cooldown leaves a checkpoint whose pruned groups are *exactly*
    zero; this surrogate applies the magnitude keep-masks to the dense
    init the same way. The target serves that checkpoint dense+b8; the
    draft is its s-sliced packed subnet — numerically the same function
    at `draft_bits=8` (the PR 4/5 slicing/packing parity contracts), so
    acceptance approaches 1 while each draft step runs at the subnet's
    ~2x-cheaper sliced shapes. This is the deployment configuration the
    speculative benchmark measures; with lower `draft_bits` the draft gets
    cheaper and acceptance becomes the measured tradeoff.

    Returns (speculative engine, baseline engine, lm) — both engines
    serve the identical target arrays, so their token streams must match
    bitwise (the benchmark asserts it)."""
    from repro.launch.engine import Engine
    cfg = get_arch(arch, smoke=smoke)
    lm = LM(cfg)
    params, _ = lm.init(jax.random.PRNGKey(seed))
    qadg, masks = resolve_keep_masks(lm, params, sparsity)
    ckpt = qadg.space.apply_masks(params, masks)
    tqparams = lm.init_qparams(ckpt)
    draft = build_draft(arch, smoke, ckpt, sparsity=sparsity,
                        bits=draft_bits, seed=seed)
    spec = Engine(lm, ckpt, tqparams, max_slots=max_slots, max_seq=max_seq,
                  draft=draft, draft_k=draft_k)
    base = Engine(lm, ckpt, tqparams, max_slots=max_slots, max_seq=max_seq)
    return spec, base, lm
