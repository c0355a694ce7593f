"""Multi-device integration: sharded train step, shard_map EP MoE, elastic
checkpoint restore across mesh shapes, and the instance-sharded cohort
engine's 4-shard differential (DESIGN.md §13).

jax locks the device count at first init, so multi-device cases run in a
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8 (tests
in this process keep seeing 1 device).
"""
import json
import subprocess
import sys
import textwrap

import pytest

SRC = "src"


def _run(code: str, device_count: int = 8) -> dict:
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        env={"PYTHONPATH": SRC,
             "XLA_FLAGS": f"--xla_force_host_platform_device_count={device_count}",
             "JAX_PLATFORMS": "cpu",  # skip the ~7-min TPU-init probe on TPU-lib images
             "PATH": "/usr/bin:/bin"},
        cwd=".",
        timeout=900,
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-3000:]}"
    return json.loads(proc.stdout.splitlines()[-1])


@pytest.mark.slow
def test_sharded_train_step_runs_and_matches_single_device():
    """2x4 mesh train step == single-device train step (same seeds)."""
    out = _run("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.data.specs import make_batch
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_host_mesh
        from repro.training.optimizer import OptConfig
        from repro.training.train_loop import TrainConfig, init_train_state, make_train_step

        cfg = get_config("granite_moe_1b").reduced().with_(d_ff=256)
        tcfg = TrainConfig(opt=OptConfig(lr=1e-3, warmup_steps=1, total_steps=10))
        rng = np.random.default_rng(0)
        batch = make_batch(rng, cfg, B=8, S=32)
        state = init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
        step = jax.jit(make_train_step(cfg, tcfg))
        ref_state, ref_metrics = step(state, batch)

        mesh = make_host_mesh(2, 4)
        state_sh = shd.train_state_shardings(cfg, mesh, tcfg)
        batch_sh = shd.batch_shardings(jax.eval_shape(lambda: batch), mesh)
        state2 = init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
        with mesh:
            step2 = jax.jit(make_train_step(cfg, tcfg),
                            in_shardings=(state_sh, batch_sh),
                            out_shardings=(state_sh, None))
            state2 = jax.device_put(state2, state_sh)
            batch2 = jax.device_put(batch, batch_sh)
            new2, m2 = step2(state2, batch2)
        dl = abs(float(ref_metrics["loss"]) - float(m2["loss"]))
        dp = max(float(jnp.abs(a - b).max()) for a, b in
                 zip(jax.tree.leaves(ref_state["params"]), jax.tree.leaves(new2["params"])))
        print(json.dumps(dict(dloss=dl, dparams=dp)))
    """)
    assert out["dloss"] < 1e-4, out
    assert out["dparams"] < 5e-3, out


@pytest.mark.slow
def test_shardmap_ep_moe_multidevice_matches_reference():
    out = _run("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models.common import init_params
        from repro.models.moe import init_router_state, moe_ffn, moe_template
        from repro.models.moe_ep import moe_ffn_ep
        from repro.launch.mesh import make_host_mesh

        cfg = get_config("granite_moe_1b").reduced().with_(
            n_experts=8, top_k=2, capacity_factor=4.0, d_ff=256)
        p = init_params(jax.random.PRNGKey(0), moe_template(cfg), jnp.float32)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((4, 16, cfg.d_model)).astype(np.float32))
        rs = init_router_state(cfg)
        y1, a1 = moe_ffn(p, x, cfg, rs)
        mesh = make_host_mesh(4, 2)  # EP=4 groups, TP=2
        with mesh:
            y2, a2 = jax.jit(lambda p_, x_: moe_ffn_ep(p_, x_, cfg, mesh, rs))(p, x)
        print(json.dumps(dict(
            dy=float(jnp.abs(y1 - y2).max()),
            dload=float(jnp.abs(a1["load"] - a2["load"]).max()),
        )))
    """)
    assert out["dy"] < 1e-4, out
    assert out["dload"] == 0.0, out


@pytest.mark.slow
def test_elastic_checkpoint_restore_across_meshes(tmp_path):
    """Save on a 2x4 mesh, restore onto 4x2 and 1x1 — elastic scaling."""
    tmp_path = str(tmp_path)
    out = _run(f"""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_host_mesh
        from repro.training.checkpoint import restore_checkpoint, save_checkpoint
        from repro.training.optimizer import OptConfig
        from repro.training.train_loop import TrainConfig, init_train_state

        cfg = get_config("stablelm_3b").reduced()
        tcfg = TrainConfig(opt=OptConfig())
        state = init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
        mesh_a = make_host_mesh(2, 4)
        sh_a = shd.train_state_shardings(cfg, mesh_a, tcfg)
        state_a = jax.device_put(state, sh_a)
        save_checkpoint({tmp_path!r}, 1, state_a)

        mesh_b = make_host_mesh(4, 2)
        sh_b = shd.train_state_shardings(cfg, mesh_b, tcfg)
        restored, _ = restore_checkpoint({tmp_path!r}, 1,
                                         jax.eval_shape(lambda: state), sh_b)
        d = max(float(jnp.abs(a - b).max()) for a, b in
                zip(jax.tree.leaves(state), jax.tree.leaves(restored)))
        shards = restored["params"]["blocks"]["mlp"]["w_gate"].sharding
        print(json.dumps(dict(d=d, resharded=str(shards.mesh.shape))))
    """)
    assert out["d"] == 0.0, out
    assert "4" in out["resharded"], out


@pytest.mark.slow
def test_sharded_cohort_multidevice_differential():
    """4-shard `EngineSpec(engine="cohort-fused", sharded=True)` == dense,
    bitwise on the dyadic tier (DESIGN.md §13): potus/shuffle/jsq, with and
    without a disruption trace, plus chunked-vs-monolithic sharded scans."""
    out = _run("""
        import json
        import numpy as np
        import jax
        from repro.core import (Component, EngineSpec, build_topology,
                                container_costs, fat_tree, rolling_restart,
                                simulate, spout_rate_matrix,
                                t_heron_placement)

        assert jax.device_count() == 4
        T = 30
        apps = [
            [Component("src", 0, True, 2, successors=(1,)),
             Component("mid", 0, False, 4, 4.0, successors=(2,)),
             Component("sink", 0, False, 2, 4.0)],
            [Component("src", 1, True, 2, successors=(1, 2), selectivity=(0.5, 0.5)),
             Component("a", 1, False, 2, 4.0, successors=(3,)),
             Component("b", 1, False, 2, 4.0, successors=(3,)),
             Component("sink", 1, False, 2, 8.0)],
        ]
        topo = build_topology(apps, gamma=64.0)
        assert topo.n_instances % 4 == 0
        sd, _ = fat_tree(4)
        net = container_costs("fat-tree", sd)
        rates = np.ones((topo.n_instances, topo.n_components))
        placement = t_heron_placement(topo, net, rates, max_per_container=4)
        rng = np.random.default_rng(11)
        unit = spout_rate_matrix(topo, 1.0)
        arr = (2.0 ** rng.integers(-1, 2, size=(T + 1, *unit.shape))).astype(np.float32)
        arr *= rng.random((T + 1, *unit.shape)) < 0.8
        arr = (arr * (unit > 0)).astype(np.float32)
        # each restarted instance belongs to a 2-instance component, so the
        # alive counts stay powers of two and every even split stays dyadic
        # (a 4 -> 3 count makes x/3 masses whose cross-shard psum
        # re-associates by 1 ulp, DESIGN.md §13.2)
        trace = rolling_restart(topo, start=8, down_slots=2,
                                instances=[1, 7, 9]).compile(topo, T, placement)

        def eq(a, b):
            return bool(np.array_equal(np.asarray(a), np.asarray(b),
                                       equal_nan=True))

        checks = {}
        for sched in ("potus", "shuffle", "jsq"):
            for tag, events in (("", None), ("+events", trace)):
                kw = dict(topo=topo, net=net, placement=placement,
                          arrivals=arr, T=T, engine="cohort-fused",
                          scheduler=sched, V=2.0, warmup=5, age_cap=32,
                          events=events)
                dense = simulate(EngineSpec(**kw))
                shard = simulate(EngineSpec(**kw, sharded=True))
                checks[sched + tag] = (
                    eq(dense.backlog, shard.backlog)
                    and eq(dense.comm_cost, shard.comm_cost)
                    and eq(dense.avg_response, shard.avg_response)
                    and float(dense.completed_mass) == float(shard.completed_mass)
                )
        kw = dict(topo=topo, net=net, placement=placement, arrivals=arr, T=T,
                  engine="cohort-fused", scheduler="potus", V=2.0, warmup=5,
                  age_cap=32, sharded=True)
        mono = simulate(EngineSpec(**kw))
        for chunk in (7, 15):
            ch = simulate(EngineSpec(**kw, chunk=chunk))
            checks[f"chunk{chunk}"] = (eq(mono.backlog, ch.backlog)
                                       and eq(mono.avg_response, ch.avg_response))
        pall = simulate(EngineSpec(**kw, use_pallas=True))
        checks["pallas_fallback"] = eq(mono.backlog, pall.backlog)
        print(json.dumps(checks))
    """, device_count=4)
    assert all(out.values()), out
