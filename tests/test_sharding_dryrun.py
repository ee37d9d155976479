"""Sharding-spec unit tests + a miniature dry-run in a subprocess (8 fake
host devices, so the main test process keeps its single real device)."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import base

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_partition_specs_shapes_divisible():
    """Every spec produced for the production mesh must evenly divide its
    dim (jit input requirement) for all archs and both step kinds."""
    from jax.sharding import PartitionSpec as P

    import repro.dist.sharding as SH
    from repro.launch import step_fns as SF

    class FakeMesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 16, "model": 16}

    mesh = FakeMesh()
    for arch in base.list_archs():
        cfg = base.get_arch(arch).FULL
        params = SF.abstract_params(cfg)
        for kind in ("train", "serve"):
            strat = SH.pick_strategy(cfg, kind)
            specs = SH.param_specs(cfg, params, mesh, train=(kind == "train"),
                                   strategy=strat)
            flat_p = jax.tree_util.tree_flatten(params)[0]
            flat_s = jax.tree.leaves(specs,
                                     is_leaf=lambda x: isinstance(x, P))
            for leaf, spec in zip(flat_p, flat_s):
                for dim, ax in zip(leaf.shape, tuple(spec)):
                    if ax is None:
                        continue
                    axes = (ax,) if isinstance(ax, str) else ax
                    size = int(np.prod([mesh.shape[a] for a in axes]))
                    assert dim % size == 0, (arch, kind, leaf.shape, spec)


def test_act_hint_noop_without_mesh():
    import jax.numpy as jnp

    from repro.dist.sharding import act_hint, set_activation_mesh

    set_activation_mesh(None)
    x = jnp.ones((4, 8))
    assert act_hint(x, "batch", "model") is x


def test_host_mesh_axes_are_auto_and_act_hint_lowers():
    """Sharding constraints are only legal on Auto mesh axes; the host mesh
    must be built that way or every act_hint under it fails to trace."""
    import jax.numpy as jnp
    from jax.sharding import AxisType

    from repro.dist.sharding import act_hint, set_activation_mesh
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh()
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)
    set_activation_mesh(mesh)
    try:
        with mesh:
            text = jax.jit(lambda x: act_hint(x * 2.0, "batch", None)).lower(
                jnp.ones((len(jax.devices()) * 2, 4))).as_text()
    finally:
        set_activation_mesh(None)
    assert "sharding" in text


def test_strategy_selection():
    from repro.dist.sharding import pick_strategy

    assert pick_strategy(base.get_arch("phi3-medium-14b").FULL,
                         "train") == "fsdp"
    assert pick_strategy(base.get_arch("phi3-medium-14b").FULL,
                         "decode") == "tp"
    assert pick_strategy(base.get_arch("mixtral-8x7b").FULL, "train") == "tp"
    assert pick_strategy(base.get_arch("mamba2-1.3b").FULL,
                         "train") == "replicated"


@pytest.mark.slow
def test_miniature_dryrun_subprocess(tmp_path):
    """Lower+compile a smoke arch on an 8-device fake mesh end to end —
    validates the whole dryrun pipeline fast."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, %r)
import dataclasses, json
import jax
from repro.configs import base
from repro.dist import sharding as SH
from repro.launch import step_fns as SF
from repro.launch import roofline as RL

from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(model_parallel=2)  # (4, 2) over the 8 host devices
mod = base.get_arch("granite-3-8b")
cfg = dataclasses.replace(mod.SMOKE, n_layers=2, scan_layers=False)
shape = base.ShapeConfig("t", 64, 8, "train")
SH.set_activation_mesh(mesh, tp=False,
                       batch_axes=("data", "model"))
params = SF.abstract_params(cfg)
pspec = SH.param_specs(cfg, params, mesh, strategy="fsdp")
tr, _ = SF.split_trainable(params, "lora")
opt = SF.abstract_opt_state(tr)
ospec = SH.opt_state_specs(pspec["lora"], opt, mesh)
batch = base.lm_input_specs(cfg, shape)
bspec = SH.batch_specs(batch, mesh, cfg, "fsdp")
sh = lambda t: SH.to_named(mesh, t)
fn = SF.make_train_step(cfg)
with mesh:
    compiled = jax.jit(fn, in_shardings=(sh(pspec), sh(ospec), sh(bspec))
                       ).lower(params, opt, batch).compile()
    ca = compiled.cost_analysis()
    coll = RL.parse_collectives(compiled.as_text())
print(json.dumps({"flops": ca.get("flops", 0),
                  "colls": sum(coll.counts.values())}))
""" % SRC  # noqa: UP031 — %r-quoting a path into a code template; an f-string would need every brace below escaped
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["flops"] > 0


def test_roofline_collective_parser():
    from repro.launch.roofline import parse_collectives, _type_bytes

    assert _type_bytes("bf16[4,8]") == 64
    assert _type_bytes("(f32[2,2], f32[4])") == 32
    hlo = """
ENTRY main {
  %x = bf16[16,128]{1,0} all-gather(%a), replica_groups={}
  %y = f32[8,8]{1,0} all-reduce(%b), to_apply=%add
}
body {
  %z = bf16[4,4]{1,0} reduce-scatter(%c)
}
"""
    st = parse_collectives(hlo)
    assert st.counts == {"all-gather": 1, "all-reduce": 1,
                         "reduce-scatter": 1}
    assert st.bytes_entry == 16 * 128 * 2 + 8 * 8 * 4 * 2  # AR counted 2x
    assert st.bytes_scanned == 4 * 4 * 2
    assert st.total(scan_steps=3) == st.bytes_entry + 3 * st.bytes_scanned


def test_input_specs_all_cells_shaped():
    """Every supported (arch x shape) produces well-formed input specs."""
    for arch in base.list_archs():
        mod = base.get_arch(arch)
        for shape in base.ALL_SHAPES:
            if not base.supports(mod.FULL, shape):
                continue
            specs = mod.input_specs(shape)
            for k, v in specs.items():
                assert hasattr(v, "shape") and hasattr(v, "dtype"), (arch, k)
            if shape.kind == "train":
                assert "labels" in specs
            if shape.kind == "decode":
                assert specs["token"].shape[0] == shape.global_batch
