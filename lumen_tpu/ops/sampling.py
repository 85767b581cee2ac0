"""Token sampling for autoregressive generation — fully jit-safe.

The reference samples on host per step with numpy
(``lumen_vlm/backends/onnxrt_backend.py:508-533``: greedy, or temperature +
top-p over a sorted copy); here sampling lives inside the compiled decode
loop so generation never round-trips to host per token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def greedy(logits: jnp.ndarray) -> jnp.ndarray:
    """[..., V] -> [...] argmax token ids."""
    return jnp.argmax(logits, axis=-1)


def _per_sample(value, logits: jnp.ndarray) -> jnp.ndarray:
    """Broadcast a scalar or per-sample [...] param against [..., V] logits."""
    v = jnp.asarray(value, jnp.float32)
    if v.ndim == logits.ndim - 1 and v.ndim > 0:
        v = v[..., None]
    return v


def apply_repetition_penalty(
    logits: jnp.ndarray, token_mask: jnp.ndarray, penalty
) -> jnp.ndarray:
    """CTRL-style penalty over tokens already generated (``token_mask``:
    [..., V] bool). Positive logits are divided, negative multiplied.
    ``penalty`` may be a scalar or per-sample [B] (batched serving mixes
    request configs in one program)."""
    penalty = _per_sample(penalty, logits)
    penalized = jnp.where(logits > 0, logits / penalty, logits * penalty)
    return jnp.where(token_mask, penalized, logits)


def top_p_filter(logits: jnp.ndarray, top_p: jnp.ndarray | float) -> jnp.ndarray:
    """Nucleus filtering: keep the smallest prefix of sorted tokens whose
    cumulative probability reaches ``top_p``; the rest get -inf."""
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
    cumulative = jnp.cumsum(sorted_probs, axis=-1)
    # Position k is kept if the cumulative mass BEFORE it is < top_p; the
    # top-1 token is always kept (top_p=0 must mean greedy, not empty set).
    keep_sorted = (cumulative - sorted_probs) < _per_sample(top_p, logits)
    keep_sorted = keep_sorted.at[..., 0].set(True)
    # Threshold logit = smallest kept logit.
    threshold = jnp.min(
        jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1, keepdims=True
    )
    return jnp.where(logits >= threshold, logits, -jnp.inf)


def draws(do_sample, temperature, xp=jnp):
    """Where the next token is drawn from the nucleus and not taken as the
    argmax: ``do_sample`` and a temperature above zero. The one predicate of
    the compiled sampler (``xp`` = ``jnp``) and of the scheduler's count of
    greedy blocks (``xp`` = ``numpy``, so the host dispatches nothing): both
    compare in float32, so the two cannot drift."""
    hot = xp.asarray(temperature, xp.float32) > xp.float32(1e-6)
    return xp.asarray(do_sample, bool) & hot


def sample(
    rng: jax.Array,
    logits: jnp.ndarray,
    temperature: jnp.ndarray | float = 1.0,
    top_p: jnp.ndarray | float = 1.0,
    do_sample: jnp.ndarray | bool = True,
) -> jnp.ndarray:
    """Temperature + top-p categorical sampling; falls back to greedy when
    ``do_sample`` is False or temperature ~ 0. All args may be traced values
    (scalars, or per-sample [B] vectors for batched mixed-config serving)
    so one compiled program serves every generation config.

    The nucleus (a sort of the whole vocabulary, a softmax, a cumulative sum,
    Gumbel noise for every entry) runs under a ``lax.cond`` on "some row
    draws": a batch of greedy rows takes the argmax alone, and a batch with
    one drawing row runs the straight line it always ran, for every row.
    ``rng`` is consumed in neither branch's favour: callers split it before
    they get here."""
    greedy_ids = greedy(logits)
    # [B]-or-scalar shaped, matching the ids
    use_sample = draws(do_sample, temperature)

    def nucleus():
        scaled = logits.astype(jnp.float32) / jnp.maximum(_per_sample(temperature, logits), 1e-6)
        filtered = top_p_filter(scaled, top_p)
        sampled_ids = jax.random.categorical(rng, filtered, axis=-1)
        return jnp.where(use_sample, sampled_ids, greedy_ids)

    return jax.lax.cond(jnp.any(use_sample), nucleus, lambda: greedy_ids)
