"""Sharding rules + a miniature dry-run in a subprocess (the device count
must be forced before jax initializes, so multi-device tests run isolated).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_arch
from repro.distributed.sharding import param_rules, spec_for_path
from repro.launch.mesh import make_host_mesh
from repro.models import model_init

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


class TestRules:
    def setup_method(self):
        self.mesh = make_host_mesh()
        self.rules = param_rules("tp_fsdp", self.mesh)

    def test_attention_projections(self):
        assert spec_for_path("layers/0/b0/q/w", self.rules, False) == \
            P("data", "model")
        assert spec_for_path("layers/0/b0/o/w", self.rules, False) == \
            P("model", "data")

    def test_scanned_groups_get_leading_none(self):
        s = spec_for_path("groups/b0/q/w", self.rules, True)
        assert s == P(None, "data", "model")

    def test_embed_vocab_sharded(self):
        assert spec_for_path("embed/table", self.rules, False) == \
            P("model", None)

    def test_norms_replicated(self):
        assert spec_for_path("layers/3/b0/ln1/scale", self.rules, False) == P()

    def test_moe_expert_dims(self):
        assert spec_for_path("groups/b0/moe/w_gate", self.rules, True) == \
            P(None, None, "data", "model")
        assert spec_for_path("groups/b0/moe/w_down", self.rules, True) == \
            P(None, None, "model", "data")

    def test_gate_params_replicated(self):
        assert spec_for_path("layers/0/b0/gate/w", self.rules, False) == P()

    def test_tp_only_profile_drops_fsdp(self):
        rules = param_rules("tp_only", self.mesh)
        assert spec_for_path("layers/0/b0/q/w", rules, False) == \
            P(None, "model")

    def test_every_param_of_every_arch_gets_valid_spec(self):
        """No rule emits a spec longer than the tensor rank, for any arch."""
        from repro.distributed.sharding import tree_param_specs
        from repro.nn.module import flatten_params
        for arch in ("granite-moe-1b-a400m", "gemma2-27b", "xlstm-1.3b",
                     "recurrentgemma-9b", "hubert-xlarge"):
            cfg = get_arch(arch).smoke()
            shapes = jax.eval_shape(
                lambda c=cfg: model_init(jax.random.PRNGKey(0), c))
            specs = tree_param_specs(shapes, "tp_fsdp", self.mesh)
            for (path, leaf), spec in zip(
                    flatten_params(shapes),
                    jax.tree_util.tree_leaves(
                        specs, is_leaf=lambda x: isinstance(x, P))):
                assert len(spec) <= leaf.ndim, (arch, path, spec)


MINI_DRYRUN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import json, dataclasses, jax
    from repro.configs import SHAPES, get_arch, apply_method
    from repro.launch.dryrun import build_lowered
    from repro.launch.roofline import analyze
    from jax.sharding import AxisType
    mesh = jax.make_mesh((4, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    spec = get_arch("{arch}")
    # reduced-width full-family config so the 16-dev compile is fast
    cfg = apply_method(spec.smoke(), "clipped_softmax")
    cfg = dataclasses.replace(cfg, scan_layers=True, remat=True,
                              max_seq_len=SHAPES["{shape}"].seq_len + 8)
    shape = dataclasses.replace(SHAPES["{shape}"], seq_len=64, global_batch=8)
    compiled = build_lowered(cfg, shape, mesh, "tp_fsdp").compile()
    roof = analyze(compiled, 16)
    print(json.dumps({{"ok": True, "bottleneck": roof.bottleneck,
                       "flops": roof.flops_per_device}}))
""")


@pytest.mark.slow  # forced 16-device subprocess compile per cell
@pytest.mark.parametrize("arch,shape", [
    ("granite-moe-1b-a400m", "train_4k"),
    ("deepseek-67b", "decode_32k"),
    ("recurrentgemma-9b", "prefill_32k"),
    ("xlstm-1.3b", "train_4k"),
])
def test_mini_dryrun_subprocess(arch, shape):
    """Lower+compile a reduced cell on a forced 16-device host mesh —
    validates the whole sharding pipeline without the 512-dev cost."""
    code = MINI_DRYRUN.format(arch=arch, shape=shape)
    env = dict(os.environ, PYTHONPATH=SRC)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["ok"] and out["flops"] > 0
