"""The parameter tree has ONE description (models/params.py: kinds,
layout, leaves) and three readers: the seeded tree is the one the code
before that description built, bit for bit, for a toy config of each
of the six configurations; the three trees have one structure; and the
modules under models/ import one another one way only."""

import hashlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.models import params as pm
from ompi_tpu.models import transformer as tfm
from ompi_tpu.models import vision

_MLA = dict(attn="mla", kv_lora_rank=8, qk_nope_dim=8, qk_rope_dim=4,
            v_head_dim=8, norm="rmsnorm", mlp_act="silu", mlp_gated=True,
            tie_head=False, router_score="sigmoid", router_bias=True,
            norm_topk_prob=True, n_shared_experts=1, n_experts=16, top_k=2,
            moe_d_ff=16, first_dense=1, held_experts=(4, 4), remat=True)
#: a toy config of each configuration the benchmark runs: every leaf
#: kind of its published config, at widths that draw in milliseconds
TOYS = {
    "opt": dict(),
    "olmoe": dict(norm="rmsnorm", pos="rope", qk_norm=True, tie_head=False,
                  moe_every=1, n_experts=8, top_k=2, mlp_act="silu",
                  mlp_gated=True, router_aux_weight=0.01,
                  router_z_weight=0.001),
    "glm5": dict(_MLA, n_layers=3, q_lora_rank=16, rope_interleave=True,
                 index_heads=2, index_dim=8, index_topk=4, mtp_layers=1,
                 mtp_weight=0.3),
    "ouro": dict(norm="rmsnorm", pos="rope", mlp_act="silu", mlp_gated=True,
                 tie_head=False, loops=2, post_norm=True, exit_gate=True,
                 exit_entropy_weight=0.05, remat=True),
    "kimivl": dict(_MLA, vision=vision.VisionConfig(
        d_model=32, n_layers=2, n_heads=4, d_ff=48, patch_dim=12,
        pos_grid=(4, 4))),
    "nemotron": dict(n_layers=4, layer_pattern="ME*E", pos="none",
                     head_width=8, n_kv_heads=2, norm="rmsnorm",
                     tie_head=False, mlp_act="relu2", moe_d_ff=16,
                     n_experts=16, top_k=2, router_score="sigmoid",
                     router_bias=True, n_shared_experts=1, shared_d_ff=24,
                     held_experts=(0, 8), ssm_heads=4, ssm_head_dim=8,
                     ssm_groups=2, ssm_state=8, ssm_chunk=8, remat=True),
}
#: sha256 of `init_params(default_rng(7), toy)` at the parent commit
#: f1a928a (PR 41), where four closures of transformer.py described the
#: tree: taken there, before models/params.py was written
PARENT = {
    "opt":
        "5e2c141c66206631fc26cc3344190b8e29aeaedae248a08d683b89862da64edb",
    "olmoe":
        "e708658ca294598d2cecf3d3389bea8b99d50940aee664ffd82c68ee17ceeef9",
    "glm5":
        "e18f2e7927bb6a8a8e5d80295547bcc9919bd74273a406ef6fa654a39cc44c52",
    "ouro":
        "66bb6e83fe29256b7f87e5e877f7f5485f2defbd6d0b7a6933379781172115af",
    "kimivl":
        "1992cc58c4f284770ca116c5c57a69ce34699dff33681a9208683148290d4034",
    "nemotron":
        "2b28e967db841b2ede9d9fcd3262ebf4e5acb5920abe7bdc711cda0372a0b3a6",
}


def toy(name: str) -> tfm.Config:
    return tfm.Config(**{"vocab": 64, "d_model": 32, "n_layers": 2,
                         "n_heads": 4, "d_ff": 48, "max_seq": 16,
                         **TOYS[name]})


def tree_sha256(tree) -> str:
    """Every leaf's path, type, shape and bytes, in the pytree's order."""
    sha = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        sha.update(f"{jax.tree_util.keystr(path)} {leaf.dtype} "
                   f"{leaf.shape}\n".encode())
        sha.update(np.ascontiguousarray(leaf).tobytes())
    return sha.hexdigest()


@pytest.mark.parametrize("name", sorted(TOYS))
def test_the_seeded_tree_is_the_parents_bit_for_bit(name):
    assert tree_sha256(tfm.init_params(
        np.random.default_rng(7), toy(name))) == PARENT[name]


@pytest.mark.parametrize("name", sorted(TOYS))
def test_the_three_readers_build_one_tree(name):
    """`param_specs` and `grad_extra_axes` have `init_params`'
    structure (what `grad_sync` flattens one up to the other by), a
    router and nothing else asks for the tp axis beside the batch's,
    and a leaf's spec has as many entries as the leaf dimensions."""
    cfg, ax = toy(name), tfm.Axes(dp="d", tp="t", ep="e")
    tree = tfm.init_params(np.random.default_rng(0), cfg)
    specs, extra = tfm.param_specs(cfg, ax), tfm.grad_extra_axes(cfg, ax)
    structure = jax.tree.structure(tree)
    assert jax.tree.structure(extra) == structure
    leaves = structure.flatten_up_to(specs)
    for (path, leaf), spec, axis in zip(
            jax.tree_util.tree_leaves_with_path(tree), leaves,
            jax.tree.leaves(extra)):
        assert len(spec) in (0, leaf.ndim), path
        assert axis == ("t" if path[-1].key == "wg" else ""), path
    described = [leaf for i in range(cfg.n_layers) for leaf in
                 pm._layer_leaves(cfg, tfm._layer_kind(cfg, i))]
    assert len({(i, leaf.path) for i, leaf in enumerate(described)}) \
        == len(described)
    assert {leaf.role for leaf in described} <= {
        pm.REPLICATED, pm.COLUMN, pm.ROW, pm.EXPERT_COLUMN, pm.EXPERT_ROW,
        pm.ROUTER}


def test_a_toy_of_every_configuration_takes_a_step():
    """The toys are configs the program computes, not only trees."""
    for name in sorted(set(TOYS) - {"kimivl"}):  # its batch is a dict
        cfg = toy(name)
        shapes = jax.eval_shape(lambda: jax.tree.map(
            jnp.asarray, tfm.init_params(np.random.default_rng(0), cfg)))
        tok = jax.ShapeDtypeStruct((2, 16), jnp.int32)
        ax = tfm.Axes()
        _, loss = jax.eval_shape(tfm.make_train_step(
            cfg, ax, tfm.param_specs(cfg, ax)), shapes, tok, tok)
        assert loss.shape == ()


def test_the_arrows_point_one_way():
    """ops <- models/remat.py <- models/vision.py <- models/transformer.py:
    the rule, its names and its wrapper, and the tower that uses them,
    load without the decoder."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys\n"
         "import ompi_tpu.models.remat, ompi_tpu.models.vision\n"
         "print('ompi_tpu.models.transformer' in sys.modules)"],
        capture_output=True, text=True, check=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "", "PYTHONPATH": ":".join(
            p for p in sys.path if p)})
    assert out.stdout.strip() == "False"
