"""Distributed behaviour tests.  These run in *subprocesses* with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main test
process (and the smoke tests) keep seeing exactly 1 device."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str) -> str:
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, %r)
        import jax, jax.numpy as jnp
        import numpy as np
        assert len(jax.devices()) == 8

        def auto_mesh(shape, axes):
            # the program shards by annotation: Auto axes, not Explicit
            return jax.make_mesh(shape, axes, axis_types=(
                jax.sharding.AxisType.Auto,) * len(axes))
    """) % os.path.join(REPO, "src") + textwrap.dedent(body)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_sharded_train_step_matches_single_device():
    out = run_sub("""
        from repro.configs import get_config, smoke_config
        from repro.launch.train import build
        from repro.launch.mesh import make_host_mesh
        from repro.data import SyntheticLMData
        import jax
        cfg = smoke_config(get_config("qwen2-1.5b"))
        data = SyntheticLMData(vocab=cfg.vocab, seq_len=16, global_batch=8)

        mesh1 = auto_mesh((1, 1), ("data", "model"))
        mesh8 = auto_mesh((4, 2), ("data", "model"))
        losses = {}
        for name, mesh in (("single", mesh1), ("sharded", mesh8)):
            state, step = build(cfg, mesh, lr=1e-2)
            ls = []
            for i in range(3):
                state, m = step(state, data.batch_at(i))
                ls.append(float(m["loss"]))
            losses[name] = ls
        for a, b in zip(losses["single"], losses["sharded"]):
            assert abs(a - b) < 2e-2, (losses)
        print("MATCH", losses["sharded"])
    """)
    assert "MATCH" in out


def test_production_mesh_axes():
    out = run_sub("""
        # make_mesh with 512 logical devices needs the flag; with 8 devices
        # we verify the function shape logic via a scaled-down equivalent.
        from repro.launch.mesh import make_host_mesh
        m = make_host_mesh(model=2)
        assert dict(m.shape) == {"data": 4, "model": 2}
        print("MESH-OK")
    """)
    assert "MESH-OK" in out


def test_compressed_psum_error_feedback():
    out = run_sub("""
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.optim.compress import compressed_psum
        import jax, jax.numpy as jnp, numpy as np
        mesh = auto_mesh((8,), ("data",))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 64))

        @partial(jax.shard_map, mesh=mesh, in_specs=P("data"),
                 out_specs=P("data"))
        def allreduce_q(gs):
            out, resid = compressed_psum(gs[0], "data")
            return (out + 0 * resid.sum())[None]

        approx = allreduce_q(g)[0]
        exact = g.mean(axis=0)
        err = float(jnp.abs(approx - exact).max())
        assert err < 0.05, err
        print("PSUM-OK", err)
    """)
    assert "PSUM-OK" in out


def test_elastic_reshard_across_meshes(tmp_path):
    out = run_sub(f"""
        from repro.configs import get_config, smoke_config
        from repro.launch.train import build
        from repro.train import checkpoint as C
        from repro.train.fault_tolerance import elastic_reshard
        from repro.nn.partitioning import param_rules, to_shardings
        from repro.train.step import train_state_specs
        from repro.data import SyntheticLMData
        import jax, numpy as np

        cfg = smoke_config(get_config("qwen2-1.5b"))
        data = SyntheticLMData(vocab=cfg.vocab, seq_len=8, global_batch=8)

        # train 2 steps on a (2,4) mesh, checkpoint
        meshA = auto_mesh((2, 4), ("data", "model"))
        state, step = build(cfg, meshA, lr=1e-2)
        for i in range(2):
            state, _ = step(state, data.batch_at(i))
        C.save({str(tmp_path)!r}, 2, state)

        # restore onto a (8,1) mesh — different DP/TP split — and continue
        meshB = auto_mesh((8, 1), ("data", "model"))
        stateB, stepB = build(cfg, meshB, lr=1e-2)
        shardingsB = jax.tree.map(lambda x: x.sharding, stateB)
        restored = elastic_reshard({str(tmp_path)!r}, 2, stateB, shardingsB)
        restored, m = stepB(restored, data.batch_at(2))

        # reference: continue on mesh A
        state, mA = step(state, data.batch_at(2))
        assert abs(float(m["loss"]) - float(mA["loss"])) < 2e-2
        print("ELASTIC-OK", float(m["loss"]), float(mA["loss"]))
    """)
    assert "ELASTIC-OK" in out
