"""EVA attention (ops/eva.py): the plain masked form and the interpreted
kernels against one position's softmax over an explicit list of keys, values
and all five gradients; what a query may read (no summary in the first
window, none of its own window); the refusals by name; what the trainer
reports of a call. Tiny sizes on the CPU: window 8, chunk 2, 2 heads."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from ray_tpu.ops import eva  # noqa: E402
from ray_tpu.ops.eva import eva_attention, eva_plan  # noqa: E402

B, H, D, W, C = 2, 2, 16, 8, 2
IMPLS = ("xla", "pallas")


def _operands(seq, seed=0, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v = (jax.random.normal(keys[i], (B, H, seq, D), dtype) for i in range(3))
    mu, phi = (jax.random.normal(keys[3 + i], (H, D), jnp.float32) for i in range(2))
    return q, k, v, mu, phi, jax.random.normal(keys[5], (B, H, seq, D), jnp.float32)


def _by_position(q, k, v, mu, phi, window, chunk):
    """One position at a time, in float64: the list of its keys is the
    positions of its window up to itself and one summary a chunk of every
    earlier window; one softmax over the list."""
    q, k, v, mu, phi = (np.asarray(x, np.float64) for x in (q, k, v, mu, phi))
    _, _, seq, d = q.shape
    scale, out = d ** -0.5, np.zeros(q.shape)
    for b in range(q.shape[0]):
        for h in range(q.shape[1]):
            kbar, vbar = [], []
            for j in range(seq // chunk):
                kc, vc = k[b, h, j * chunk:(j + 1) * chunk], v[b, h, j * chunk:(j + 1) * chunk]
                a, bw = np.exp(scale * kc @ mu[h]), np.exp(scale * kc @ phi[h])
                kbar.append((a / a.sum()) @ kc)
                vbar.append((bw / bw.sum()) @ vc)
            for i in range(seq):
                first = (i // window) * window
                keys = list(k[b, h, first:i + 1]) + kbar[:first // chunk]
                values = list(v[b, h, first:i + 1]) + vbar[:first // chunk]
                scores = np.array([scale * q[b, h, i] @ key for key in keys])
                p = np.exp(scores - scores.max())
                out[b, h, i] = (p / p.sum()) @ np.array(values)
    return out


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("windows", [1, 3, 4])
def test_values_against_one_positions_softmax_over_its_list_of_keys(impl, windows):
    with jax.default_matmul_precision("highest"):
        q, k, v, mu, phi, _ = _operands(windows * W)
        out = eva_attention(q, k, v, mu, phi, window=W, chunk=C, implementation=impl)
    np.testing.assert_allclose(np.asarray(out), _by_position(q, k, v, mu, phi, W, C), atol=2e-6)


@pytest.mark.parametrize("impl", IMPLS)
def test_all_five_gradients_against_the_references(impl):
    """q, k, v, mu, phi: against the benchmark's plain reference (its own
    blocked-by-window softmax over the concatenated list, (B, S, H, D)
    layout), differentiated by JAX."""
    from benchmark.reference import evabyte_ref

    seq = 4 * W
    q, k, v, mu, phi, cot = _operands(seq, seed=3)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda *a: jnp.sum(eva_attention(*a, window=W, chunk=C, implementation=impl) * cot),
                       argnums=(0, 1, 2, 3, 4))(q, k, v, mu, phi)
        t = lambda x: jnp.swapaxes(x, 1, 2)     # noqa: E731 - (B, H, S, D) <-> (B, S, H, D)
        want = jax.grad(lambda q, k, v, mu, phi: jnp.sum(
            evabyte_ref._eva(t(q), t(k), t(v), mu, phi, W, C) * t(cot)), argnums=(0, 1, 2, 3, 4))(
                q, k, v, mu, phi)
    for name, a, b in zip(("q", "k", "v", "mu", "phi"), got, want):
        assert float(jnp.max(jnp.abs(b))) > 0.1, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-6, err_msg=name)


@pytest.mark.parametrize("impl", IMPLS)
def test_a_first_window_query_reads_no_summary(impl):
    """Other pooling vectors move every later window's output and leave the
    first window's as it was, to the bit."""
    q, k, v, mu, phi, _ = _operands(3 * W, seed=1)
    run = lambda mu, phi: np.asarray(eva_attention(      # noqa: E731
        q, k, v, mu, phi, window=W, chunk=C, implementation=impl))
    base, moved = run(mu, phi), run(mu + 1.0, phi - 1.0)
    assert np.array_equal(base[:, :, :W], moved[:, :, :W])
    assert np.abs(base[:, :, W:] - moved[:, :, W:]).max() > 1e-3


@pytest.mark.parametrize("impl", IMPLS)
def test_a_query_never_reads_a_summary_of_its_own_window(impl):
    """Another key and value at the LAST position of the second window: the
    summary of its chunk moves, and in that window only the last query (which
    reads the position itself) does; every query of the third window does."""
    q, k, v, mu, phi, _ = _operands(3 * W, seed=2)
    last = 2 * W - 1
    k2, v2 = k.at[:, :, last].add(1.0), v.at[:, :, last].add(1.0)
    run = lambda k, v: np.asarray(eva_attention(         # noqa: E731
        q, k, v, mu, phi, window=W, chunk=C, implementation=impl))
    base, moved = run(k, v), run(k2, v2)
    assert np.array_equal(base[:, :, :last], moved[:, :, :last])
    assert np.abs(base[:, :, last] - moved[:, :, last]).max() > 1e-3
    assert np.abs(base[:, :, 2 * W:] - moved[:, :, 2 * W:]).min(axis=-1).max() > 0
    assert (np.abs(base[:, :, 2 * W:] - moved[:, :, 2 * W:]).max(axis=-1) > 1e-6).all()


@pytest.mark.parametrize("impl", IMPLS)
def test_bfloat16_operands_stay_close_to_the_float32_form(impl):
    q, k, v, mu, phi, _ = _operands(3 * W, seed=4)
    want = _by_position(q, k, v, mu, phi, W, C)
    got = eva_attention(*(x.astype(jnp.bfloat16) for x in (q, k, v)), mu, phi, window=W, chunk=C,
                        implementation=impl)
    assert got.dtype == jnp.bfloat16
    assert np.abs(np.asarray(got, np.float32) - want).max() < 0.06


@pytest.mark.parametrize("change, match", [
    (dict(seq=3 * W + 4), "is no multiple of window 8"),
    (dict(window=6, chunk=4), "window 6 is no multiple of chunk 4"),
    (dict(kv_heads=1), "no grouped keys"),
    (dict(mu_shape=(H, D + 1)), r"mu and phi are \(heads, head_dim\)"),
    (dict(implementation="cuda"), "unknown attention implementation"),
], ids=["sequence-not-whole-windows", "window-not-whole-chunks", "grouped-keys", "pooling-vector-shape",
        "unknown-implementation"])
def test_refusals_name_what_is_wrong(change, match):
    seq = change.get("seq", 3 * W)
    q = jnp.zeros((B, H, seq, D))
    k = jnp.zeros((B, change.get("kv_heads", H), seq, D))
    mu = jnp.zeros(change.get("mu_shape", (H, D)))
    with pytest.raises(ValueError, match=match):
        eva_attention(q, k, k, mu, mu, window=change.get("window", W), chunk=change.get("chunk", C),
                      implementation=change.get("implementation", "xla"))


def test_the_far_kernels_walk_takes_groups_of_windows_then_the_rest(monkeypatch):
    """The same numbers whatever the walk's strip and group (a group that does
    not divide the windows before a query's leaves a remainder)."""
    q, k, v, mu, phi, _ = _operands(6 * W, seed=5)
    outs = []
    for strip, group in ((1024, 4), (4, 1), (8, 2), (2, 3)):
        monkeypatch.setattr(eva, "_FAR_STRIP", strip)
        monkeypatch.setattr(eva, "_FAR_GROUP", group)
        outs.append(np.asarray(eva_attention(q, k, v, mu, phi, window=W, chunk=C, implementation="pallas")))
    for other in outs[1:]:
        np.testing.assert_allclose(other, outs[0], atol=2e-6)


def test_plan_counts_the_local_and_the_far_tiles():
    """32,768 positions, window 2,048, chunk 16: 16 windows of 2 x 2 flash
    tiles (3 live a window), 120 (window, earlier window) far tiles a head,
    2,048 summaries; four pallas calls in a differentiated call."""
    plan = eva_plan(32768, window=2048, chunk=16, head_dim=128, implementation="pallas")
    assert plan == {"eva_impl": "pallas", "eva_window": 2048, "eva_chunk": 16, "eva_summaries": 2048,
                    "eva_local_tiles": 48, "eva_far_tiles": 120, "eva_kernels": 4}
    plain = eva_plan(24, window=8, chunk=2, head_dim=16, implementation="xla")
    assert (plain["eva_impl"], plain["eva_far_tiles"], plain["eva_local_tiles"], plain["eva_kernels"]) == (
        "xla", 3, 0, 0)


def test_under_a_mesh_the_kernels_run_once_a_shard_and_the_pooling_vectors_gradient_is_summed():
    """fsdp=4 over a batch of 4: the kernels inside one shard_map (q, k, v and
    the summaries by batch), the gradient of mu and phi the sum over the
    shards, equal to one device's."""
    from jax.sharding import NamedSharding, PartitionSpec

    from ray_tpu.parallel import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(fsdp=4), devices=jax.devices()[:4])
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    q, k, v = (jax.random.normal(keys[i], (4, H, 3 * W, D)) for i in range(3))
    mu, phi = (jax.random.normal(keys[3 + i], (H, D)) for i in range(2))

    def loss(q, k, v, mu, phi):
        return jnp.sum(jnp.square(eva_attention(q, k, v, mu, phi, window=W, chunk=C, implementation="pallas")))

    want = jax.grad(loss, argnums=(0, 3, 4))(q, k, v, mu, phi)
    batch = NamedSharding(mesh, PartitionSpec(("dp", "fsdp")))
    whole = NamedSharding(mesh, PartitionSpec())

    def sharded(q, k, v, mu, phi):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return jax.grad(loss, argnums=(0, 3, 4))(q, k, v, mu, phi)

    step = jax.jit(sharded, in_shardings=(batch, batch, batch, whole, whole))
    assert "shard_map" in str(jax.make_jaxpr(sharded)(q, k, v, mu, phi))
    for a, b in zip(step(q, k, v, mu, phi), want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)
