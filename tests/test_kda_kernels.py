"""The delta rule's core on its Pallas kernels (ops/kda.py:
`kda_delta_fwd`, and behind the `custom_vjp` the reverse-grid
`kda_delta_bwd`) in interpret mode on the CPU against their oracle,
`chunked_delta`'s `jax.numpy`, and against the recurrence token by
token: the output, the last state and the cotangent of ALL five
operands — decays near 0 and near 1, beta near 2, chunks of 16 and 64,
one head a grid step and two, a sequence of one chunk, bfloat16 —, and
a term dropped in either kernel showing (tests/test_kda.py holds the
mixer, the rule and the compiles for a described v5e)."""

import jax
import jax.numpy as jnp
import pytest

from test_kda import CORE_REGIMES, _core_operands, close, highest, recurrence

from ompi_tpu.ops import kda


def _weighed(fn):
    def loss(*args):
        o, last = fn(*args)
        return (o.astype(jnp.float32) * jnp.cos(jnp.arange(o.size).reshape(
            o.shape))).sum() + (last * last).sum()
    return loss


def _values_and_cotangents(fn, args):
    """[o, last and the five cotangents under `_weighed`] of a core."""
    (_, out), grads = highest(jax.value_and_grad(
        lambda *a: (_weighed(fn)(*a), fn(*a)), (0, 1, 2, 3, 4),
        has_aux=True), *args)
    return list(out) + list(grads)


def _both_forms(args, chunk, per):
    """`_values_and_cotangents` of the kernels (interpret mode) and of
    their ``jax.numpy`` oracle."""
    return tuple(_values_and_cotangents(
        lambda *a: kda.chunked_delta(*a, chunk, p), args) for p in (per, None))


@pytest.mark.parametrize("chunk, per, regime", [
    (chunk, per, regime) for chunk, per in ((16, 1), (64, 2))
    for regime in sorted(CORE_REGIMES)] + [(16, 2, "as_drawn"),
                                           (64, 1, "as_drawn")])
def test_the_cores_kernels_are_the_numpy_core(kernels_on_cpu, chunk, per,
                                              regime):
    """The kernel form (interpret mode: ``kda_delta_fwd`` and, behind
    the ``custom_vjp``, the reverse-grid ``kda_delta_bwd``) against
    `chunked_delta`'s ``jax.numpy``: the output, the last state and the
    cotangent of ALL five operands, float32 — two chunks a sequence,
    one head a grid step and two."""
    args = _core_operands(1, 2, 2 * chunk, 2, 8, **CORE_REGIMES[regime])
    for a, r in zip(*_both_forms(args, chunk, per)):
        assert float(jnp.abs(r).max()) > 0
        # a fast decay's sums reach hundreds inside a chunk: a
        # difference of two keeps float32's ABSOLUTE error, and the two
        # forms add a chunk's terms in another order
        close(a, r, 1e-3 if regime == "fast_decay" else 1e-5)


@pytest.mark.parametrize("chunk, per", [(16, 2), (64, 1)])
def test_the_cores_kernels_round_as_the_numpy_core(kernels_on_cpu, chunk,
                                                   per):
    """In bfloat16 both forms round the same operands of the same
    products; the kernels' cotangents stay float32 where autodiff's
    are rounded."""
    args = _core_operands(1, 2, 128, 4, 8, jnp.bfloat16)
    for a, r in zip(*_both_forms(args, chunk, per)):
        assert float(jnp.abs(r.astype(jnp.float32)).max()) > 0
        close(a, r, 2e-2)


@pytest.mark.parametrize("chunk", [16, 64])
def test_the_cores_kernels_on_one_chunk_are_the_recurrence(kernels_on_cpu,
                                                           chunk):
    """A sequence of ONE chunk (the grid's first step is its last)
    against the recurrence token by token: values and cotangents."""
    args = _core_operands(5, 1, chunk, 2, 8)

    def by_tokens(q, k, v, g, beta):
        o, last = recurrence(q[0], k[0], v[0], g[0], beta[0])
        return o[None], last[None]

    mine, _ = _both_forms(args, chunk, 1)
    for a, r in zip(mine, _values_and_cotangents(by_tokens, args)):
        close(a, r, 5e-4, atol=1e-7)


@pytest.mark.parametrize("fault", ["pairs_unread", "pairs_cotangent_lost"])
def test_a_term_dropped_in_a_kernel_shows(kernels_on_cpu, monkeypatch,
                                          fault):
    """What the comparisons above would miss if they could miss
    anything: the ``P V'`` term of the output dropped in the forward
    kernel moves o, and the pairs' cotangent dropped in the backward
    kernel moves dq and dk, far over their tolerance."""
    args = _core_operands(2, 1, 128, 2, 8)

    def kernels(*a):
        return kda.chunked_delta(*a, 64, 1)

    sound = _values_and_cotangents(kernels, args)
    if fault == "pairs_unread":
        within = kda._within
        monkeypatch.setattr(kda, "_within", lambda *a: dict(
            within(*a), pairs=jnp.zeros((64, 64))))
    else:
        back = kda._carry_back
        monkeypatch.setattr(kda, "_carry_back", lambda *a: dict(
            back(*a), pairs=jnp.zeros((64, 64))))
    lost = _values_and_cotangents(kernels, args)
    moved = [float(jnp.abs(a - b).max() / jnp.abs(b).max())
             for a, b in zip(lost, sound)]
    if fault == "pairs_unread":
        assert moved[0] > 0.05
    else:
        assert moved[0] == moved[1] == 0.0
        assert moved[2] > 0.05 and moved[3] > 0.05
