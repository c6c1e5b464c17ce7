"""The grouped expert kernel (``kernels.moe_experts``) in interpret mode
against its plain per-expert oracle (``ref.py``), on routings that stress
the grouped layout: uneven groups, a held expert with no rows, every row on
one expert, and picks of experts held elsewhere."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.moe_experts import ops, ref

T, K, E, D, F = 12, 3, 4, 128, 256


def _weights(seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(ks[0], (T, D), jnp.float32)
    w1 = jax.random.normal(ks[1], (E, D, F), jnp.float32) / D ** 0.5
    w3 = jax.random.normal(ks[2], (E, D, F), jnp.float32) / D ** 0.5
    w2 = jax.random.normal(ks[3], (E, F, D), jnp.float32) / F ** 0.5
    return x, w1, w3, w2


def _routing(case):
    rng = np.random.default_rng(7)
    if case == "uneven":
        # 21 rows on expert 0 (two tiles), a few on 1 and 3, ids held
        # elsewhere (-1, E) mixed in
        ids = rng.choice([0, 0, 0, 0, 1, 3, -1, E], size=(T, K))
    elif case == "empty_expert":
        ids = rng.choice([0, 1, 3], size=(T, K))          # 2 has no rows
    elif case == "one_expert":
        ids = np.full((T, K), 2)
    else:
        raise ValueError(case)
    w = rng.uniform(0.05, 1.0, size=(T, K))
    return jnp.asarray(ids, jnp.int32), jnp.asarray(w, jnp.float32)


@pytest.mark.parametrize("case", ["uneven", "empty_expert", "one_expert"])
def test_kernel_matches_per_expert_oracle(case):
    x, w1, w3, w2 = _weights()
    ids, w = _routing(case)
    sizes = np.asarray(ops.group_sizes(ids, E))
    if case == "empty_expert":
        assert sizes[2] == 0
    if case == "one_expert":
        assert sizes.tolist() == [0, 0, T * K, 0]
    got = ops.moe_experts(x, ids, w, w1, w3, w2, interpret=True)
    want = ref.moe_experts(x, ids, w, w1, w3, w2)
    # float32 both ways; only the order of the per-token sums differs
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_tiles_cover_any_routing():
    """The layout's static tile count holds every routing: each expert
    with rows pads to whole tiles."""
    for n, e in ((96, 16), (384, 16), (5, 64), (36, 4)):
        worst = max(sum(-(-s // ops.TM) for s in sizes)
                    for sizes in ([n] + [0] * (e - 1),
                                  [1] * min(n, e) + [0] * max(0, e - n),
                                  [n // e] * (e - 1) + [n - n // e * (e - 1)]))
        assert worst <= ops.max_tiles(n, e)
