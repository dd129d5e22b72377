"""The dropless expert-share layer and the granite hybrid family at reduced
size on the CPU: shares of a partition of the experts add up to the whole
layer, no assignment is dropped under skewed routing, a row's loss depends on
no other row, and the scorer's one call equals row-by-row calls."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.model_zoo import build_model
from repro.models.moe import moe_share, share_bounds

E, K, D, F = 8, 3, 64, 32


def _layer_weights(seed=0, skew=0.0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    router = jax.random.normal(k[0], (D, E)) / 8
    router = router.at[:, 0].add(skew)  # skew: expert 0 wins nearly every token
    return (router, jax.random.normal(k[1], (E, D, 2 * F)) / 8,
            jax.random.normal(k[2], (E, F, D)) / 6)


def _x(seed=1, b=4, s=16):
    x = jax.random.normal(jax.random.PRNGKey(seed), (b, s, D))
    return x + 0.5  # a shared direction, so a skewed router column wins


def _uncut(x, router, wgu, wd):
    """Every expert, a plain sum per token: top K of the router's logits, a
    softmax over them, each chosen expert's SwiGLU weighted by its gate."""
    x = np.asarray(x, np.float64).reshape(-1, D)
    logits = x @ np.asarray(router, np.float64)
    out = np.zeros_like(x)
    for t in range(len(x)):
        top = np.argsort(-logits[t], kind="stable")[:K]
        g = np.exp(logits[t, top] - logits[t, top].max())
        g /= g.sum()
        for gate, e in zip(g, top):
            hu = x[t] @ np.asarray(wgu[e], np.float64)
            h = hu[:F] / (1 + np.exp(-hu[:F])) * hu[F:]
            out[t] += gate * (h @ np.asarray(wd[e], np.float64))
    return out


def _share(x, weights, held):
    router, wgu, wd = weights
    ids = jnp.asarray(held)
    return jax.jit(lambda xx: moe_share(xx, router, wgu[ids], wd[ids], held, K))(x)


@pytest.mark.parametrize("skew", [0.0, 4.0])
def test_shares_of_a_partition_add_up_to_the_whole_layer(skew):
    """For every held set of a partition of the 8 experts, the shares' outputs
    added up equal the uncut layer over all experts (the shared expert is
    the same on every chip, so it is counted once and left out here)."""
    x, w = _x(), _layer_weights(skew=skew)
    parts = [(0, 1), (2, 3), (4, 5), (6, 7)]
    total = sum(np.asarray(_share(x, w, p)[0], np.float64) for p in parts)
    np.testing.assert_allclose(total.reshape(-1, D), _uncut(x, *w), rtol=2e-4, atol=2e-5)


def test_skewed_routing_drops_no_assignment():
    """Expert 0 wins nearly every token: every assignment to a held expert is
    computed, and the slots count them all."""
    x, w = _x(), _layer_weights(skew=4.0)
    out, slots = _share(x, w, (0, 1))
    slots = np.asarray(slots)
    assert (slots == 0).sum() >= 0.9 * slots.shape[0] * slots.shape[1]  # the skew holds
    # expert 0 alone gets more rows than a capacity layer's 1.25 * T * K / E
    assert (slots == 0).sum() > 1.25 * 64 * K / E
    router, wgu, wd = w
    held_only = _uncut(x, router, wgu.at[2:].set(0.0), wd.at[2:].set(0.0))
    np.testing.assert_allclose(np.asarray(out, np.float64).reshape(-1, D), held_only,
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("skewed", [(), (0, 1)])
def test_both_row_bounds_compute_every_held_assignment(skewed):
    """1,024 tokens: a balanced router's held assignments fit the fast
    bound; with both held experts winning nearly every token they need the
    full one. Either way the output is the uncut layer's held part."""
    fast, full = share_bounds(1024, K, 2, E)
    assert (fast, full) == (1536, 2048)
    router, wgu, wd = _layer_weights()
    for e in skewed:
        router = router.at[:, e].add(4.0)
    x = _x(b=8, s=128)
    out, slots = _share(x, (router, wgu, wd), (0, 1))
    total = int((np.asarray(slots) < 2).sum())
    assert (total > fast) if skewed else (total <= fast), total
    held_only = _uncut(x, router, wgu.at[2:].set(0.0), wd.at[2:].set(0.0))
    np.testing.assert_allclose(np.asarray(out, np.float64).reshape(-1, D), held_only,
                               rtol=2e-4, atol=2e-5)


def _granite(skew=0.0):
    cfg = get_config("granite-4.0-h-small-ep8", reduced=True)
    model = build_model(cfg, remat_policy="none")
    params = model.init_params(jax.random.PRNGKey(0))
    if skew:  # every token shares a direction, and every router sends it to expert 0
        params["embed"] = params["embed"] + jnp.asarray(0.05, params["embed"].dtype)
        for kind in ("mamba", "attention"):
            params[kind]["router"] = params[kind]["router"].at[:, :, 0].add(skew)
    return cfg, model, params


def _batch(rows, seq=16, vocab=512, seed=0):
    rng = np.random.default_rng(seed)
    toks = rng.integers(4, vocab, (rows, seq)).astype(np.int32)
    mask = np.ones((rows, seq), np.float32)
    mask[1, 9:] = 0.0  # a short row
    return toks, np.roll(toks, -1, axis=1), mask


def test_a_rows_loss_does_not_depend_on_its_batch():
    """Under skewed routing, row 0 scored among two different sets of other
    rows gets the same loss: nothing is dropped for capacity."""
    from repro.ops.model_ops import batch_scorer

    _, model, params = _granite(skew=2.0)
    step = batch_scorer(model, with_stats=True)
    a = _batch(8, seed=1)
    b = tuple(np.concatenate([x[:1], y[1:]]) for x, y in zip(a, _batch(8, seed=2)))
    (la, sa), (lb, sb) = step(params, *a), step(params, *b)
    assert float(la[0]) == pytest.approx(float(lb[0]), abs=1e-6)
    assert not np.allclose(np.asarray(la[1:]), np.asarray(lb[1:]))
    # expert 0 of each layer takes nearly every scored position, far more
    # than a capacity layer's 1.25 * positions * top_k / experts
    rows = np.asarray(sa["moe_rows"])
    assert rows.shape == (4, 2) and np.all(rows[:, 0] > 0.9 * a[2].sum())


def test_held_rows_count_the_scored_positions_only():
    """With every expert held, each scored position counts top_k
    assignments in each layer, and padded positions count none."""
    from repro.ops.model_ops import batch_scorer

    cfg = dataclasses.replace(get_config("granite-4.0-h-small-ep8", reduced=True),
                              experts_held=tuple(range(8)))
    model = build_model(cfg, remat_policy="none")
    params = model.init_params(jax.random.PRNGKey(0))
    toks, labels, mask = _batch(4)
    _, stats = batch_scorer(model, with_stats=True)(params, toks, labels, mask)
    np.testing.assert_array_equal(np.asarray(stats["moe_rows"]).sum(axis=1),
                                  np.full(4, K * mask.sum()))


@pytest.mark.parametrize("arch", ["mamba2-1.3b", "granite-4.0-h-small-ep8"])
def test_batch_scorer_one_call_equals_row_by_row(arch, monkeypatch):
    """In the program's math at float32: the CPU picks its matmul kernels by
    shape, so a batch of one and of six round differently in the last bit,
    and bfloat16 casts of the float32 residual stream can turn that into an
    ulp (test_a_rows_loss_does_not_depend_on_its_batch compares one shape)."""
    import repro.models.layers as L
    from repro.ops.model_ops import batch_scorer

    monkeypatch.setattr(L, "COMPUTE_DTYPE", np.float32)
    cfg = dataclasses.replace(get_config(arch, reduced=True), param_dtype="float32")
    model = build_model(cfg, remat_policy="none")
    params = model.init_params(jax.random.PRNGKey(0))
    toks, labels, mask = _batch(6)
    step = batch_scorer(model)
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(step(params, toks, labels, mask))
        alone = np.array([float(step(params, toks[i:i + 1], labels[i:i + 1], mask[i:i + 1])[0])
                          for i in range(6)])
    np.testing.assert_allclose(whole, alone, rtol=1e-5, atol=1e-5)
    # and each row's loss is its mean over its own masked positions
    with jax.default_matmul_precision("highest"):
        loss1, _ = model.loss_fn(params, {"tokens": toks[1:2], "labels": labels[1:2],
                                          "loss_mask": mask[1:2]})
    assert float(loss1) == pytest.approx(whole[1], rel=1e-5, abs=1e-5)
