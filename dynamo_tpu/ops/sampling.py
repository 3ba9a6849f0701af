"""In-jit batched token sampling: greedy / temperature / top-k / top-p,
with sampled-token logprobs, repetition/frequency/presence penalties and
optional per-request seeds.

The reference forwards `SamplingOptions` (reference:
lib/llm/src/protocols/common.rs:248) into vLLM; here sampling runs on-device
inside the jitted decode step so no logits ever cross to the host. Per-slot
parameters are arrays, so one compiled sampler serves a mixed batch.

Top-k/top-p operate on a fixed `CANDIDATES`-wide shortlist (lax.top_k) —
per-request k is a clamp within it, p a cumulative cutoff over it. This is
exact for k <= CANDIDATES and a negligible-mass approximation for top-p
(identical to common GPU serving practice, TPU-friendly static shape).

Logprobs are of the sampled token under the raw (pre-temperature,
pre-penalty) model distribution — the convention the OpenAI API reports.

Penalties follow the OpenAI definitions over "the text so far" (prompt +
completion, one shared count buffer):
  frequency: logit -= frequency_penalty * count(token)
  presence:  logit -= presence_penalty  * (count(token) > 0)
  repetition (vLLM/HF-style): seen tokens' positive logits are divided by
  the penalty, negative multiplied.

Per-request seeds derive each row's key as
fold_in(fold_in(key(seed), position), 1) — reproducible across runs and
independent of whatever else shares the batch (vLLM's per-request
generator semantics). Rows with seed < 0 use the engine's stream key.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CANDIDATES = 64  # shortlist width for top-k/top-p
TOP_LOGPROBS_MAX = 8  # alternatives width (engine carry shapes match)


def apply_penalties(
    logits: jnp.ndarray,        # [B, V] f32
    counts: jnp.ndarray,        # [B, V] int8 token occurrence counts
    freq_pen: jnp.ndarray,      # [B] f32 (0 = off)
    pres_pen: jnp.ndarray,      # [B] f32 (0 = off)
    rep_pen: jnp.ndarray,       # [B] f32 (1 = off)
) -> jnp.ndarray:
    cnt = counts.astype(jnp.float32)
    seen = cnt > 0
    logits = logits - freq_pen[:, None] * cnt
    logits = logits - pres_pen[:, None] * seen.astype(jnp.float32)
    rep = rep_pen[:, None]
    penalized = jnp.where(logits > 0, logits / rep, logits * rep)
    return jnp.where(seen, penalized, logits)


def _per_row_keys(base_key: jax.Array, seeds: jnp.ndarray, positions: jnp.ndarray):
    """[B] keys: seeded rows get a run-independent key derived from
    (seed, position); unseeded rows split the batch key."""

    def row_key(seed, pos, batch_key):
        seeded = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(seed), pos), 1
        )
        return jax.lax.cond(seed >= 0, lambda: seeded, lambda: batch_key)

    batch_keys = jax.random.split(base_key, seeds.shape[0])
    return jax.vmap(row_key)(seeds, positions, batch_keys)


def _shortlist_mask(scaled, top_k, top_p):
    """THE sampling distribution, shared by `sample_tokens` and
    `verify_draft_tokens` — speculative verification preserves the
    sampled distribution only while both consult the exact same
    shortlist + top-k/top-p mask, so keep this the single copy.

    approx_max_k: TPU-native shortlist (exact top_k sorts the whole
    vocab on the VPU — measurably slow at 128k). recall_target=0.95 on
    a 64-wide shortlist is indistinguishable for sampling.

    Takes scaled logits [N, V] with per-row top_k [N] / top_p [N];
    returns (cand_ids [N, C] i32, masked shortlist logits [N, C] with
    excluded candidates at -1e30)."""
    v = scaled.shape[-1]
    if jax.default_backend() == "tpu" and v > 4096:
        cand_logits, cand_ids = jax.lax.approx_max_k(
            scaled, min(CANDIDATES, v), recall_target=0.95
        )
    else:
        cand_logits, cand_ids = jax.lax.top_k(scaled, min(CANDIDATES, v))
    n = cand_logits.shape[-1]
    ranks = jnp.arange(n)

    k = jnp.where(top_k <= 0, n, jnp.minimum(top_k, n))
    keep_k = ranks[None, :] < k[:, None]

    probs = jax.nn.softmax(cand_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # keep tokens whose *preceding* cumulative mass is below p (always >= 1 token)
    keep_p = (cum - probs) < top_p[:, None]

    masked = jnp.where(keep_k & keep_p, cand_logits, -1e30)
    return cand_ids.astype(jnp.int32), masked


@jax.named_scope("sample")  # a name for profiles (models/llama.py)
def sample_tokens(
    logits: jnp.ndarray,       # [B, V] float
    key: jax.Array,            # PRNG key
    temperature: jnp.ndarray,  # [B] f32 (<= 0 treated as greedy)
    top_k: jnp.ndarray,        # [B] i32 (<= 0 means disabled)
    top_p: jnp.ndarray,        # [B] f32 (>= 1 means disabled)
    all_greedy: bool = False,  # static: whole batch greedy -> argmax only
    return_logprobs: bool = False,  # static: also return sampled logprob [B]
    counts: jnp.ndarray | None = None,      # [B, V] int8 (penalties on)
    freq_pen: jnp.ndarray | None = None,    # [B] f32
    pres_pen: jnp.ndarray | None = None,    # [B] f32
    rep_pen: jnp.ndarray | None = None,     # [B] f32
    seeds: jnp.ndarray | None = None,       # [B] i32 (-1 = engine stream key)
    positions: jnp.ndarray | None = None,   # [B] i32 (seed derivation)
    top_n: int = 0,            # static: also return top-n alternatives
):
    """Returns sampled ids [B] i32; with `return_logprobs` adds the
    sampled logprob [B] f32; with `top_n` > 0 additionally the top-n
    alternative ids [B, n] + their raw-distribution logprobs [B, n]
    (OpenAI `top_logprobs`).

    `all_greedy` is a trace-time flag the engine sets when no live slot
    samples (the common serving case): it skips the shortlist machinery
    entirely — approx_max_k costs ~2 ms at [64, 128k] on v5e, argmax
    fuses into the logits matmul."""
    b, v = logits.shape
    raw = logits.astype(jnp.float32)

    def picked_logprobs(ids):
        logz = jax.nn.logsumexp(raw, axis=-1)
        picked = jnp.take_along_axis(raw, ids[:, None], axis=-1)[:, 0]
        return picked - logz

    def top_alternatives():
        # EXACT top_k: unlike the internal sampling shortlist, these are
        # API output — an approx_max_k miss would drop the true best
        # tokens (even the sampled one) from the user-visible list
        n = min(top_n, v)
        t_lg, t_ids = jax.lax.top_k(raw, n)
        logz = jax.nn.logsumexp(raw, axis=-1, keepdims=True)
        return t_ids.astype(jnp.int32), t_lg - logz

    logits = raw
    if counts is not None:
        logits = apply_penalties(logits, counts, freq_pen, pres_pen, rep_pen)

    greedy_ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if all_greedy:
        if return_logprobs and top_n > 0:
            return (greedy_ids, picked_logprobs(greedy_ids), *top_alternatives())
        if return_logprobs:
            return greedy_ids, picked_logprobs(greedy_ids)
        return greedy_ids

    is_greedy = temperature <= 0.0
    temp = jnp.where(is_greedy, 1.0, temperature)
    scaled = logits / temp[:, None]

    cand_ids, masked = _shortlist_mask(scaled, top_k, top_p)
    if seeds is not None:
        keys = _per_row_keys(key, seeds, positions)
        choice = jax.vmap(lambda kk, row: jax.random.categorical(kk, row))(
            keys, masked
        )
    else:
        choice = jax.random.categorical(key, masked, axis=-1)  # [B] shortlist idx
    sampled_ids = jnp.take_along_axis(cand_ids, choice[:, None], axis=-1)[:, 0]

    ids = jnp.where(is_greedy, greedy_ids, sampled_ids).astype(jnp.int32)
    if return_logprobs and top_n > 0:
        return (ids, picked_logprobs(ids), *top_alternatives())
    if return_logprobs:
        return ids, picked_logprobs(ids)
    return ids


@jax.named_scope("sample")
def sample_block(
    logits: jnp.ndarray,       # [R * B, V]: a block of B positions a row,
    # flat (a [R, B, V] array pads its B rows to a tile of 8: at B = 4 twice
    # the bytes, and a copy to flatten it)
    masked: jnp.ndarray,       # [R, B] bool: the position still holds a mask
    key: jax.Array,
    temperature: jnp.ndarray,  # [R] f32 (<= 0 treated as greedy)
    top_k: jnp.ndarray,        # [R] i32
    top_p: jnp.ndarray,        # [R] f32
    *,
    n_fill: int,               # static: masked positions a pass fills
    mask_token_id: int,        # static: never sampled, outside the softmax
    all_greedy: bool = False,
    return_logprobs: bool = False,
    top_n: int = 0,
):
    """One denoising pass's sampling and TRANSFER for a model generated by
    diffusion over blocks: position i's logits predict position i's token
    (no shift). Every position samples (greedy / temperature / top-k /
    top-p a row, as `sample_tokens`, the mask token's logit excluded from
    the softmax); the pass then fills `min(n_fill, masked positions)` of
    a row's masked positions, the leftmost (the `sequential` transfer, the
    one a configuration may state: `models/config.py: _from_sdar_moe`).

    Returns (ids [R, B] i32, fill [R, B] bool, logprobs [R, B] f32[, top
    ids [R, B, n], top logprobs [R, B, n]]): `ids` is the sampled token
    at every position, `fill` the masked positions this pass fills; the
    log-probabilities are zeros unless the caller asks (`return_logprobs`)."""
    r, b = masked.shape
    v = logits.shape[-1]
    flat = jnp.where(jnp.arange(v) == mask_token_id, -jnp.inf,
                     logits.astype(jnp.float32))
    out = sample_tokens(
        flat, key, jnp.repeat(temperature, b), jnp.repeat(top_k, b),
        jnp.repeat(top_p, b), all_greedy=all_greedy,
        return_logprobs=return_logprobs,
        top_n=top_n if return_logprobs else 0,
    )
    out = out if isinstance(out, tuple) else (out,)
    ids = out[0].reshape(r, b)
    lps = (out[1].reshape(r, b) if return_logprobs
           else jnp.zeros((r, b), jnp.float32))
    rank = jnp.cumsum(masked, axis=1) - 1              # among the masked
    fill = masked & (rank < n_fill)
    tops = tuple(a.reshape(r, b, -1) for a in out[2:])
    return (ids, fill, lps, *tops)


@jax.named_scope("sample")
def verify_draft_tokens(
    logits: jnp.ndarray,       # [B, T, V] float; row j is the model's
    #                            distribution for position pos0 + j + 1
    draft: jnp.ndarray,        # [B, T-1] i32 drafted tokens
    draft_len: jnp.ndarray,    # [B] i32 valid draft count per row (0..T-1)
    key: jax.Array,
    temperature: jnp.ndarray,  # [B] f32 (<= 0 treated as greedy)
    top_k: jnp.ndarray,        # [B] i32 (<= 0 means disabled)
    top_p: jnp.ndarray,        # [B] f32 (>= 1 means disabled)
    all_greedy: bool = False,  # static: whole batch greedy
):
    """Speculative-decoding verification over a batch of drafted windows.

    The engine ran ONE model step over [carry, d_1, .., d_k] and `logits`
    holds the target distribution at every window position — either a
    standalone verify dispatch (`_spec_verify_step`) or the decode rows
    of a MIXED step (`_mixed_model_step`, where prefill rows ride along
    with draft_len=0: their window column 0 is then exactly the plain
    sampler's draw and n_emit is 1). Acceptance:

    - greedy rows: exact match — d_j is accepted iff it equals the argmax
      at position j-1, so the emitted stream is byte-identical to the
      non-speculative engine;
    - sampled rows: rejection sampling against the proposer's point-mass
      draft q — accept d_j with probability p_j(d_j) (the same
      shortlist/top-k/top-p-masked distribution `sample_tokens` uses),
      and on rejection resample from p_j with d_j masked out (the exact
      residual distribution for a point-mass q), so the emitted stream
      has the same distribution as the non-speculative sampler.

    After the leading accepted run of length a (bounded by draft_len) one
    extra token is always emitted: the rejection resample at slot a, or —
    when every draft was accepted — a bonus token from the unmodified
    distribution at slot a. Returns (out_tokens [B, T] i32, n_emit [B]
    i32 in [1, T]); out positions >= n_emit are garbage.
    """
    b, t, v = logits.shape
    kd = t - 1
    raw = logits.astype(jnp.float32)
    greedy_ids = jnp.argmax(raw, axis=-1).astype(jnp.int32)  # [B, T]
    valid = jnp.arange(kd)[None, :] < draft_len[:, None]     # [B, K]
    g_match = (draft == greedy_ids[:, :kd]) & valid

    if all_greedy:
        # accepted drafts ARE the argmaxes, so the output at every
        # position is just the argmax; only the emit count varies
        lead = jnp.cumprod(g_match.astype(jnp.int32), axis=1)
        return greedy_ids, jnp.sum(lead, axis=1).astype(jnp.int32) + 1

    is_greedy = temperature <= 0.0
    temp = jnp.where(is_greedy, 1.0, temperature)
    scaled = raw / temp[:, None, None]

    # the same CANDIDATES-wide shortlist + top-k/top-p mask the engine's
    # sampler applies (ONE shared implementation — `_shortlist_mask` —
    # so the preserved target distribution cannot drift from the one
    # the non-speculative path actually samples from); per-row params
    # repeat across the t window positions
    cand_ids, masked = _shortlist_mask(
        scaled.reshape(b * t, v),
        jnp.repeat(top_k, t), jnp.repeat(top_p, t),
    )
    n = cand_ids.shape[-1]
    cand_ids = cand_ids.reshape(b, t, n)
    masked = masked.reshape(b, t, n)
    p_masked = jax.nn.softmax(masked, axis=-1)  # [B, T, C]

    key_u, key_r, key_b = jax.random.split(key, 3)
    # acceptance: p_j(d_j) under the masked distribution (0 when the
    # draft is outside the shortlist/top-k/top-p mask -> reject)
    is_draft = cand_ids[:, :kd, :] == draft[:, :, None]      # [B, K, C]
    p_draft = jnp.sum(jnp.where(is_draft, p_masked[:, :kd], 0.0), axis=-1)
    u = jax.random.uniform(key_u, (b, kd))
    accept = jnp.where(is_greedy[:, None], g_match, (u < p_draft) & valid)

    lead = jnp.cumprod(accept.astype(jnp.int32), axis=1)     # [B, K]
    a = jnp.sum(lead, axis=1).astype(jnp.int32)

    # rejection resample at each draft slot: residual of a point-mass q
    # is p with d_j removed, renormalized
    masked_r = jnp.where(is_draft, -1e30, masked[:, :kd])
    r_choice = jax.random.categorical(key_r, masked_r, axis=-1)
    r_ids = jnp.take_along_axis(
        cand_ids[:, :kd], r_choice[..., None], axis=-1
    )[..., 0]
    # bonus sample at every slot (used at slot a when a == draft_len)
    b_choice = jax.random.categorical(key_b, masked, axis=-1)
    b_ids = jnp.take_along_axis(cand_ids, b_choice[..., None], axis=-1)[..., 0]
    r_ids = jnp.where(is_greedy[:, None], greedy_ids[:, :kd], r_ids)
    b_ids = jnp.where(is_greedy[:, None], greedy_ids, b_ids)

    head = jnp.where(
        lead.astype(bool), draft, jnp.where(valid, r_ids, b_ids[:, :kd])
    )
    out = jnp.concatenate([head, b_ids[:, kd:]], axis=1).astype(jnp.int32)
    return out, a + 1


def count_tokens(
    counts: jnp.ndarray,   # [B, V] int8
    row: jnp.ndarray,      # scalar i32 slot
    tokens: jnp.ndarray,   # [T] i32 (0-padded; token id 0 never counted)
) -> jnp.ndarray:
    """Scatter-add a prompt's tokens into one slot's count row (saturating
    int8; pad token id 0 is ignored). Used at admission so penalties see
    the prompt, not just the completion."""
    onehot = jnp.zeros((counts.shape[1],), jnp.int32).at[tokens].add(
        jnp.where(tokens > 0, 1, 0)
    )
    new_row = jnp.minimum(counts[row].astype(jnp.int32) + onehot, 127).astype(
        jnp.int8
    )
    return counts.at[row].set(new_row)


def bump_counts(
    counts: jnp.ndarray,    # [B, V] int8
    tokens: jnp.ndarray,    # [B] i32 sampled this step
    active: jnp.ndarray,    # [B] bool
) -> jnp.ndarray:
    """Per-step count update for the sampled tokens (saturating int8)."""
    rows = jnp.arange(tokens.shape[0])
    cur = counts[rows, tokens].astype(jnp.int32)
    inc = jnp.where(active, 1, 0)
    return counts.at[rows, tokens].set(
        jnp.minimum(cur + inc, 127).astype(jnp.int8)
    )
