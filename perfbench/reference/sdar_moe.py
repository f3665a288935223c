"""Plain reference of JetLM/SDAR-30B-A3B-Chat (``sdar_moe``: qwen3_moe's
decoder under block-causal attention, generating by diffusion over blocks).

The forward pass of one whole sequence in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: a materialised ``[T, T]``
block-causal mask, a Python loop over layers and over experts, no cache, no
kernels, no batching, no grouped or sorted dispatch; and the sampler of
``generate.py::block_diffusion_generate`` as a plain loop over blocks and
passes, every pass a whole forward of the sequence so far. It takes the
ENGINE's parameter tree (``models.llama.init_params``), so the same seeded
weights go through both.

For layer input h [T, d], eps = ``rms_norm_eps``, B = ``block_length``:

    x = RMSNorm(h);  q = x W_q -> [T, 32, 128];  k, v = x W_k, x W_v -> [T, 4, 128]
    q, k = RMSNorm over the 128 dims of every head (learned weight each)
    RoPE(theta 1e6) at the absolute position over the whole head, pairs (i, i + 64)
    P = softmax over keys j with j // B <= i // B of q k^T / sqrt(128), 8 q heads a kv head
    h += (P v) W_o
    x2 = RMSNorm(h);  p = softmax(x2 W_r) over all 128;  idx = top_8(p)
    w = p[idx] / sum p[idx];  h += sum_k w_k W_down,k (silu(x2 W_gate,k) * x2 W_up,k)
    logits = RMSNorm(h) W_head       (position i's logits predict position i)

Generation (``generate``): the prompt's first ``B * (len // B)`` tokens are
final; the other ``len % B`` are the first positions of the first open
block. A DENOISING pass runs [everything final | the block, masked positions
as ``mask_token_id``'s embedding] and, at each masked position, takes the
candidate (greedy: the arg max) and its confidence (its softmax probability);
it transfers every masked position whose confidence exceeds
``confidence_threshold`` if there are at least ``n = B / denoising_steps`` of
them, else the ``n`` most confident (ties to the lower position). A full
block is final (the published sampler runs a COMMIT pass over it to store its
K/V; with no cache here that pass computes nothing anybody reads, so it is
counted and not run). Output ends at ``max_tokens``.

Departures from the publication, all of them:

- **No cache, so no commit pass** (above): its effect, that later blocks see
  the block's FINAL ids, is what recomputing the whole sequence gives.
- **Confidence at temperature 0** is the softmax probability of the arg max
  under the raw logits (the published sampler divides by the temperature
  first and cannot be called at 0). The engine's sampled requests take the
  probability its sampler reports (temperature-scaled, before top-k/top-p
  truncation); the published one takes it after truncation. Greedy requests,
  which are what the goldens and the tests compare, agree exactly.
- **Masked is a flag**, never ``id == mask_token_id``: a prompt may hold
  151669.
- **Ties**: ``lax.top_k``'s rule for the router (the lower index), the lower
  position for the transfer.
- ``remasking`` is ``low_confidence_dynamic`` only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

TOP_N = 5


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """x: [T, n, hd]; half-split rotation of (x[i], x[i + hd/2])."""
    half = x.shape[-1] // 2
    inv_freq = theta ** -(np.arange(half, dtype=np.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(lp, cfg, h, positions, mask):
    T = h.shape[0]
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    x = _rms(h, lp["input_norm"], cfg.rms_norm_eps)
    q = (x @ lp["wq"]).reshape(T, nh, hd)
    k = (x @ lp["wk"]).reshape(T, nkv, hd)
    v = (x @ lp["wv"]).reshape(T, nkv, hd)
    q = _rope(_rms(q, lp["q_norm"], cfg.rms_norm_eps), positions,
              cfg.rope_theta)
    k = _rope(_rms(k, lp["k_norm"], cfg.rms_norm_eps), positions,
              cfg.rope_theta)
    g = nh // nkv
    s = jnp.einsum("tkgd,skd->kgts", q.reshape(T, nkv, g, hd), k) \
        / np.sqrt(hd)
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("kgts,skd->tkgd", p, v).reshape(T, nh * hd)
    return h + o @ lp["wo"]


def _experts(lp, cfg, x):
    """-> (the experts' sum [T, d], the router's margin a token: its k-th
    score over its (k+1)-th)."""
    p = jax.nn.softmax(x @ lp["router"], axis=-1)                # [T, E]
    k = cfg.num_experts_per_tok
    top, idx = jax.lax.top_k(p, k + 1)
    margin = top[:, k - 1] - top[:, k]
    idx, w = idx[:, :k], top[:, :k]
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(cfg.num_experts):
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)      # [T]
        f32 = lambda a: jnp.asarray(a[e], jnp.float32)           # noqa: E731
        y = y + w_e[:, None] * (
            (jax.nn.silu(x @ f32(lp["w_gate"])) * (x @ f32(lp["w_up"])))
            @ f32(lp["w_down"]))
    return y, margin


@functools.partial(jax.jit, static_argnums=1)
def _layer(lp, cfg, h, positions, mask):
    """One layer, traced once a sequence length (the loop over the experts
    unrolls): the same plain operations, without a dispatch each."""
    lp = {k: (a if k.startswith("w_") else jnp.asarray(a, jnp.float32))
          for k, a in lp.items()}
    h = _attention(lp, cfg, h, positions, mask)
    y, margin = _experts(lp, cfg, _rms(h, lp["post_attn_norm"],
                                       cfg.rms_norm_eps))
    return h + y, margin


def block_causal_mask(T: int, block: int, causal_inside: bool = False):
    """[T, T] bool: key j visible to query i iff j // B <= i // B
    (``causal_inside``: a planted fault, the causal mask)."""
    i = np.arange(T)
    if causal_inside:
        return jnp.asarray(i[None, :] <= i[:, None])
    return jnp.asarray(i[None, :] // block <= i[:, None] // block)


def forward(params, cfg, token_ids, precision="highest", masked=None,
            margins=None, causal_inside=False, unseen=None):
    """token_ids: [T] ints of ONE sequence; ``masked``: [T] bools, True
    where the input is the mask token's embedding whatever the id ->
    logits [T, vocab] float32, row t the distribution AT position t.
    ``precision``: anything but "highest" is a DEGRADED reading.
    ``margins``: a list that is given, an expert layer, every token's
    router margin (the 8th score over the 9th). ``unseen``: [T] bools, keys
    no LATER block sees (a planted fault: a commit that never wrote)."""
    if any(a.dtype == jnp.int8 for a in jax.tree.leaves(params)):
        raise ValueError("the reference takes dense-precision weights, not "
                         "a quantized tree")
    tokens = np.asarray(token_ids, np.int32)
    if masked is not None:
        tokens = np.where(np.asarray(masked, bool), cfg.mask_token_id, tokens)
    T = tokens.shape[0]
    positions = jnp.arange(T, dtype=jnp.int32)
    mask = block_causal_mask(T, cfg.block_length, causal_inside)
    if unseen is not None:
        blk = np.arange(T) // cfg.block_length
        mask = mask & ~(jnp.asarray(np.asarray(unseen, bool))[None, :]
                        & jnp.asarray(blk[None, :] < blk[:, None]))
    stack = params["layers"]
    with jax.default_matmul_precision(precision):
        h = jnp.asarray(params["embed"][tokens], jnp.float32)
        for l in range(stack["wq"].shape[0]):
            # (the experts stay as stored until each is used: a layer's 128
            # in float32 are 2.4 GB at the published widths)
            h, margin = _layer({k: a[l] for k, a in stack.items()}, cfg, h,
                               positions, mask)
            if margins is not None:
                margins.append(np.asarray(margin))
        x = _rms(h, jnp.asarray(params["final_norm"], jnp.float32),
                 cfg.rms_norm_eps)
        return x @ jnp.asarray(params["lm_head"], jnp.float32)


def transfer(confidence, masked, n: int, threshold: float):
    """The transfer rule over one block, as a plain loop: numpy [B] float
    and [B] bool -> [B] bool."""
    B = len(masked)
    high = [bool(masked[i]) and confidence[i] > threshold for i in range(B)]
    if sum(high) >= n:
        return np.asarray(high)
    order = sorted((i for i in range(B) if masked[i]),
                   key=lambda i: (-confidence[i], i))
    out = np.zeros(B, bool)
    out[order[:n]] = True
    return out


def generate(params, cfg, prompt, max_tokens: int, precision="highest",
             stop_ids=(), trace=None, **faults):
    """``block_diffusion_generate`` at temperature 0 over ONE prompt ->
    {"tokens", "logprobs", "top"}: the output in position order, and for
    each token the log-probability and the top-5 of the pass that
    transferred it, at its position. ``trace``: a list that is given one
    dict a denoising pass (the block's start, its masked flags before, the
    confidences, what was transferred, the logits of the block [B, V] when
    ``trace_logits``). ``faults`` (tests): ``pick_second`` transfers the
    second most confident position, ``causal_inside`` masks causally inside
    a block, ``shift`` reads position i's candidate from row i - 1,
    ``stale_kv`` lets later blocks see a generated block as its FIRST
    denoising pass had it (K/V written from a denoising pass: masked
    inputs), ``skip_commit`` hides a generated block from later ones (its
    K/V never written)."""
    B, n = cfg.block_length, cfg.block_length // cfg.denoising_steps
    seq = list(prompt)
    start = len(seq) - len(seq) % B
    ids = seq[start:] + [0] * (B - len(seq) % B)
    masked = [False] * (len(seq) % B) + [True] * (B - len(seq) % B)
    marks = [None] * B
    first = start + B       # where the blocks that are all generated begin
    out = {"tokens": [], "logprobs": [], "top": [], "passes": 0}

    def leave(i):       # position start + i is final and in order
        lp, top = marks[i]
        out["tokens"].append(ids[i])
        out["logprobs"].append(lp)
        out["top"].append(top)
        seq.append(ids[i])
        return (len(out["tokens"]) >= max_tokens or ids[i] in stop_ids)

    while True:
        while any(masked):
            out["passes"] += 1
            margins = [] if trace is not None else None
            gen = [i >= first for i in range(start)]    # generated blocks
            logits = forward(
                params, cfg, seq[:start] + ids, precision,
                masked=(gen if faults.get("stale_kv") else [False] * start)
                + masked, margins=margins,
                causal_inside=faults.get("causal_inside", False),
                unseen=(gen + [False] * B if faults.get("skip_commit")
                        else None))
            rows = logits[start - 1:start - 1 + B] if faults.get("shift") \
                else logits[start:]
            lps = np.asarray(jax.nn.log_softmax(rows.astype(jnp.float32)))
            cand = lps.argmax(axis=-1)
            conf = np.exp(lps[np.arange(B), cand])
            xfer = transfer(conf, masked, n, cfg.confidence_threshold)
            if faults.get("pick_second") and sum(masked) > 1 \
                    and xfer.sum() == 1:
                order = sorted((i for i in range(B) if masked[i]),
                               key=lambda i: (-conf[i], i))
                xfer = np.zeros(B, bool)
                xfer[order[1]] = True
            if trace is not None:
                trace.append({
                    "start": start, "masked": list(masked),
                    "confidence": [float(c) for c in conf],
                    "transferred": [bool(x) for x in xfer],
                    "router_margin_min": float(min(
                        m[start:].min() for m in margins))})
            for i in np.nonzero(xfer)[0]:
                top = np.argsort(-lps[i], kind="stable")[:TOP_N]
                ids[i], masked[i] = int(cand[i]), False
                marks[i] = (float(lps[i, cand[i]]),
                            {str(int(t)): float(lps[i, t]) for t in top})
            at = len(seq) - start
            while at < B and not masked[at]:
                if leave(at):
                    return out
                at += 1
        out["passes"] += 1          # the commit pass (not run: see above)
        start += B
        ids, masked, marks = [0] * B, [True] * B, [None] * B
