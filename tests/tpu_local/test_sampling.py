"""sample_tokens against the sort-based function it replaced (CPU).

The reference below is that function's body verbatim, returning its two
masks and its running sum beside the tokens. The same key has to give the
same tokens wherever the masks agree, and the masks have to agree except on
a row whose running sum passes ``top_p`` within float32 rounding of a
summation order.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mcp_context_forge_tpu.tpu_local import sampling
from mcp_context_forge_tpu.tpu_local.sampling import SamplingParams, sample_tokens

ROWS = 8
VERIFY_K = 4        # the verify path samples [B * K, V], a row's K positions alike
NEAR = 1e-5


def _reference(logits, params, key):
    B, V = logits.shape
    greedy = jnp.argmax(logits, axis=-1)

    temp = jnp.maximum(params.temperature, 1e-6)[:, None]
    scaled = logits / temp

    # top-k: mask everything below the k-th logit (k=0 disables)
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]                # [B,V]
    k = jnp.clip(params.top_k, 0, V)
    kth_index = jnp.where(k > 0, k - 1, V - 1)
    kth_value = jnp.take_along_axis(sorted_desc, kth_index[:, None], axis=1)
    topk_mask = jnp.where((k > 0)[:, None], scaled >= kth_value, True)

    # top-p (nucleus): smallest set with cumulative prob >= p
    probs_sorted = jax.nn.softmax(sorted_desc, axis=-1)
    cumulative = jnp.cumsum(probs_sorted, axis=-1)
    cutoff_count = jnp.sum(cumulative < params.top_p[:, None], axis=-1) + 1  # [B]
    cutoff_index = jnp.clip(cutoff_count - 1, 0, V - 1)
    cutoff_value = jnp.take_along_axis(sorted_desc, cutoff_index[:, None], axis=1)
    topp_mask = scaled >= cutoff_value

    masked = jnp.where(topk_mask & topp_mask, scaled, -jnp.inf)
    sampled = jax.random.categorical(key, masked, axis=-1)
    tokens = jnp.where(params.temperature <= 0.0, greedy, sampled)
    return tokens, topk_mask & topp_mask, scaled, cumulative


def _logits(kind: str, V: int, rows: int) -> jax.Array:
    key = jax.random.PRNGKey(V)
    if kind == "random":
        return 3.0 * jax.random.normal(key, (rows, V), jnp.float32)
    # a handful of levels (both zeros among them), so every cut falls in a tie
    levels = jnp.asarray([-2.5, -1.0, -0.0, 0.0, 0.5, 2.0, 4.0], jnp.float32)
    return levels[jax.random.randint(key, (rows, V), 0, levels.shape[0])]


def _temperatures(rows_kind: str) -> np.ndarray:
    sampled = np.asarray([0.7, 1.0, 1.3, 0.2, 0.7, 2.0, 1.0, 0.5], np.float32)
    if rows_kind == "greedy":
        return np.zeros((ROWS,), np.float32)
    if rows_kind == "sampled":
        return sampled
    mixed = np.where(np.arange(ROWS) % 2 == 0, 0.0, sampled).astype(np.float32)
    return np.repeat(mixed, VERIFY_K) if rows_kind == "verify" else mixed


_sample = jax.jit(sample_tokens)
_reference_jit = jax.jit(_reference)
_filtered = jax.jit(sampling._filtered)


@pytest.mark.parametrize("rows_kind", ["greedy", "sampled", "mixed", "verify"])
@pytest.mark.parametrize("top_p", [1.0, 0.9, 0.1, 1e-6])
@pytest.mark.parametrize("top_k", [0, 1, 5, "V"])
@pytest.mark.parametrize("V", [257, 4096])
@pytest.mark.parametrize("kind", ["random", "tied"])
def test_same_tokens_and_masks_as_the_sort(kind, V, top_k, top_p, rows_kind):
    temperature = _temperatures(rows_kind)
    rows = temperature.shape[0]
    logits = _logits(kind, V, rows)
    params = SamplingParams(
        jnp.asarray(temperature),
        jnp.full((rows,), V if top_k == "V" else top_k, jnp.int32),
        jnp.full((rows,), top_p, jnp.float32))
    greedy_rows = temperature <= 0.0
    # the masks follow the parameters alone, the tokens the key too
    _, want_mask, scaled, cumulative = _reference_jit(
        logits, params, jax.random.PRNGKey(0))
    mask = np.isfinite(np.asarray(_filtered(scaled, params)))
    same_mask = (mask == np.asarray(want_mask)).all(axis=1)
    near = (np.abs(np.asarray(cumulative) - top_p) <= NEAR).any(axis=1)
    assert (same_mask | near).all(), "masks differ away from the cut"
    # the tier the batch takes: tier 2 draws from the unmasked row
    filters = (~greedy_rows & ((params.top_k > 0) | (top_p < 1.0))).any()
    held = greedy_rows | (same_mask if filters else want_mask.all(axis=1))
    # the excuse must not swallow the case: top_p 1.0 is itself a sum within
    # rounding of its cut, every other case has to be held on most rows
    assert top_p == 1.0 or held.sum() >= 2 * rows // 3
    for seed in range(3):
        key = jax.random.PRNGKey(seed)
        tokens = np.asarray(_sample(logits, params, key))
        assert tokens.shape == (rows,) and tokens.dtype == np.int32
        # greedy rows take the argmax whatever tier the batch is in
        np.testing.assert_array_equal(
            tokens[greedy_rows], np.argmax(np.asarray(logits), -1)[greedy_rows])
        want = np.asarray(_reference_jit(logits, params, key)[0])
        np.testing.assert_array_equal(tokens[held], want[held])


def _primitives(jaxpr, inside_cond=False):
    """(primitive name, whether under a cond branch) of every equation."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, inside_cond
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(
                sub, inside_cond or eqn.primitive.name == "cond")


def test_no_step_sorts_and_a_greedy_step_only_takes_the_argmax():
    params = SamplingParams(jnp.zeros((ROWS,)), jnp.zeros((ROWS,), jnp.int32),
                            jnp.ones((ROWS,)))
    jaxpr = jax.make_jaxpr(sample_tokens)(
        jnp.zeros((ROWS, 257)), params, jax.random.PRNGKey(0))
    found = list(_primitives(jaxpr.jaxpr))
    names = {name for name, _ in found}
    assert "sort" not in names and "cumsum" not in names
    assert {"argmax", "cond", "scan", "random_bits"} <= names
    # outside the branches: the argmax and the two scalars of the parameters
    outside = {name for name, inside in found if not inside}
    assert "argmax" in outside
    assert outside <= {"argmax", "cond", "gt", "lt", "and", "or", "reduce_or",
                       "convert_element_type"}, outside
