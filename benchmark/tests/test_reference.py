"""The plain references against the program at toy width on the CPU,
the operation counts against a hand count, and the seeded weights
against the library's tree."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark import compare, flops, weights  # noqa: E402
from benchmark.reference import allreduce_sum  # noqa: E402
from benchmark.reference import opt_decoder as ref  # noqa: E402
from benchmark.runners import train_step as ts  # noqa: E402

TOY = {"vocab": 512, "d_model": 128, "n_layers": 2, "n_heads": 4,
       "d_ff": 512, "max_seq": 64, "param_dtype": "float32"}


def _program(sizes):
    from ompi_tpu.models import transformer as tfm

    cfg = tfm.Config(vocab=sizes["vocab"], d_model=sizes["d_model"],
                     n_layers=sizes["n_layers"], n_heads=sizes["n_heads"],
                     d_ff=sizes["d_ff"], max_seq=sizes["max_seq"],
                     dtype=jnp.float32,
                     param_dtype=jnp.dtype(sizes["param_dtype"]))
    return tfm, cfg


def test_forward_and_loss_match_the_program_in_float32():
    """Program computing in float32: agreement to float32 rounding
    (1e-4 on logits of order 1: a few hundred roundings of 6e-8
    through two layers), so the equations are the same equations."""
    tfm, cfg = _program(TOY)
    params = weights.device_init(TOY, 7)
    toks, labs = weights.batches(TOY["vocab"], 2, 3, 48, 7)
    got = tfm.forward_local(params, toks[0], cfg, tfm.Axes())
    want = ref.logits(params, toks[0], TOY["n_heads"])
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)
    nll, cnt = tfm.loss_local(params, toks[0], labs[0], cfg, tfm.Axes())
    assert abs(float(nll / cnt)
               - float(ref.loss(params, toks[0], labs[0],
                                TOY["n_heads"]))) < 1e-5


def test_sgd_step_matches_the_program_step():
    """Three steps of the reference's layer-by-layer SGD against the
    program's jitted train step, both float32: same losses, same
    movement of every leaf."""
    params = weights.device_init(TOY, 3)
    start = weights.device_init(TOY, 3)
    toks, labs = weights.batches(TOY["vocab"], 3, 4, 32, 3)
    tfm, cfg = _program(TOY)
    ax = tfm.Axes()
    step = jax.jit(tfm.make_train_step(cfg, ax, tfm.param_specs(cfg, ax),
                                       lr=0.01))
    p_losses = []
    for i in range(3):
        params, loss = step(params, toks[i], labs[i])
        p_losses.append(float(loss))
    moved = compare.leaf_delta_norms(params, start)
    r_losses, _, r_moved = ts.reference_steps(TOY, toks, labs, 3, 0.01, 3)
    np.testing.assert_allclose(p_losses, r_losses, rtol=1e-5)
    assert compare.worst_leaf_gap(moved, r_moved) < 1e-3


def test_flops_against_a_hand_count():
    cfg = {"d_model": 8, "d_ff": 32, "vocab": 100, "n_layers": 2}
    # per layer: wq wk wv wo 4 x 64 = 256, w1 w2 2 x 256 = 512 -> 768
    # two layers 1536, tied head 800 -> 2336 matmul parameters
    assert flops.matmul_params(cfg) == 2336
    # 6 x 2336 = 14016; attention 6 x L2 x T16 x d8 = 1536
    assert flops.train_flops_per_token(cfg, 16) == 14016 + 1536
    assert flops.train_flops_per_step(cfg, 3, 16) == (14016 + 1536) * 48
    # all-reduce of 1000 B over 4 ranks: 2 x 3/4 x 1000
    from benchmark.collectives import allreduce

    assert allreduce.bus_bytes(1000, 4) == 1500.0


def test_flops_of_the_committed_cells():
    from benchmark import manifest as mf

    sizes = ts.model_sizes(mf.load_json("configs", "opt-30b.json"))
    # 3 x (4 x 7168^2 + 2 x 7168 x 28672) + 50272 x 7168
    assert flops.matmul_params(sizes) == 2_210_037_760
    per_tok = flops.train_flops_per_token(sizes, 1024)
    assert per_tok == 6 * 2_210_037_760 + 6 * 3 * 1024 * 7168
    # the tied head's share of the matmul work at this depth
    assert 0.16 < 50272 * 7168 / 2_210_037_760 < 0.17


def test_seeded_tree_is_the_librarys_tree():
    tfm, cfg = _program(dict(TOY, param_dtype="bfloat16"))
    lib = tfm.init_params(np.random.default_rng(0), cfg)
    mine = weights.device_init(dict(TOY, param_dtype="bfloat16"), 0)
    sig = lambda t: jax.tree.map(  # noqa: E731
        lambda a: (tuple(a.shape), str(a.dtype)), t)
    assert sig(lib) == sig(mine)
    for a, b in zip(jax.tree.leaves(lib), jax.tree.leaves(mine)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if a.std() > 0:  # same scale, not the same draw
            assert 0.8 < b.std() / a.std() < 1.25
        else:
            assert (a == b).all()
    again = weights.device_init(dict(TOY, param_dtype="bfloat16"), 0)
    assert all((np.asarray(x) == np.asarray(y)).all() for x, y in zip(
        jax.tree.leaves(mine), jax.tree.leaves(again)))
    big = weights.device_init(dict(TOY, param_dtype="bfloat16"),
                              2**31 + 5)
    assert not (np.asarray(big["embed"]) == np.asarray(
        weights.device_init(dict(TOY, param_dtype="bfloat16"),
                            5)["embed"])).all()


def test_allreduce_reference_and_its_control():
    """The plain sum accepts any order of float32 additions, refuses a
    reduction carried in bfloat16 (the control), and the linear fold is
    exact about order."""
    seed, n, ranks = 2**31 + 11, 4096, 4
    xs = [allreduce_sum.rank_input(seed, 1, r, n, "float32")
          for r in range(ranks)]
    fwd = (xs[0] + xs[1]) + (xs[2] + xs[3])
    assert allreduce_sum.result_gap(fwd, seed, 1, ranks, n, "float32",
                                    1024) < 2e-7
    control = allreduce_sum.result_gap(None, seed, 1, ranks, n, "float32",
                                       1024, control_dtype="bfloat16")
    assert control > 1e-4
    linear = ((xs[0] + xs[1]) + xs[2]) + xs[3]
    assert allreduce_sum.linear_fold_mismatches(
        linear, seed, 1, ranks, n, "float32") == 0
    assert allreduce_sum.linear_fold_mismatches(
        xs[3] + xs[2] + xs[1] + xs[0], seed, 1, ranks, n, "float32") > 0
    dropped = xs[0] + xs[1] + xs[2]  # a rank left out
    assert allreduce_sum.result_gap(dropped, seed, 1, ranks, n, "float32",
                                    1024) > 1e-3
