"""Multi-device tests — run in a subprocess with 8 forced host devices so
the main pytest process keeps its single-device view (per assignment, the
device-count flag must never be set globally)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow  # minutes: each test spawns an 8-device subprocess

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_in_subprocess(code: str) -> dict:
    # the forced devices are host devices: pin the child to the CPU so it
    # never reaches for an accelerator its parent may hold
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu", PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sharded_join_vs_oracle():
    res = run_in_subprocess(textwrap.dedent("""
        import json, numpy as np, jax
        from jax.sharding import Mesh
        from repro.core import (Caps, Pattern, build_store, execute_sharded,
                                execute_oracle, rows_set)
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        rng = np.random.RandomState(3)
        tr = np.stack([rng.randint(0, 60, 600), rng.randint(100, 105, 600),
                       rng.randint(0, 60, 600)], 1).astype(np.int32)
        store = build_store(tr, num_shards=8)
        pats = [Pattern("?x", 101, "?y"), Pattern("?y", 102, "?z")]
        want, ovars = execute_oracle(tr, pats)
        ok = True
        for mode in ("mapsin", "reduce"):
            caps = Caps(out_cap=2048, probe_cap=32, bucket_cap=1024)
            t, v, ovf, vars_ = execute_sharded(store, pats, mesh, mode,
                                               caps=caps)
            got = rows_set(t, v, len(vars_))
            if vars_ != ovars:
                perm = [vars_.index(x) for x in ovars]
                got = set(tuple(r[i] for i in perm) for r in got)
            ok = ok and (got == want) and int(np.asarray(ovf).sum()) == 0
        print(json.dumps({"ok": ok, "n": len(want)}))
    """))
    assert res["ok"] and res["n"] > 0


def test_sharded_a2a_matches_broadcast():
    """routing="a2a" (point-to-point all_to_all dispatch) is bit-identical
    to the broadcast reference on an 8-shard mesh — including a fat
    rdf:type-style row whose range spans >= 2 region splits, exercising the
    multi-destination fan-out and the shard-order offset composition, and a
    star query taking the multiway single-row-GET path."""
    res = run_in_subprocess(textwrap.dedent("""
        import json, numpy as np, jax
        from jax.sharding import Mesh
        from repro.core import (Caps, ExecConfig, Pattern, build_store,
                                execute_sharded, execute_oracle, rows_set)
        from repro.core.rdf import BITS, pack3
        from repro.core.triple_store import range_intersects_region
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        rng = np.random.RandomState(3)
        HUB = 70
        tr = np.stack([rng.randint(0, 60, 600), rng.randint(100, 105, 600),
                       rng.randint(0, 60, 600)], 1).astype(np.int32)
        fat = np.stack([np.full(300, HUB), np.full(300, 102),
                        np.arange(300) % 90], 1).astype(np.int32)
        link = np.stack([rng.randint(0, 60, 200), np.full(200, 101),
                         np.full(200, HUB)], 1).astype(np.int32)
        tr = np.concatenate([tr, fat, link])
        store = build_store(tr, num_shards=8)
        lo = pack3(np.int64(HUB), np.int64(0), np.int64(0))
        sp = np.asarray(store.splits_spo)
        spans = int(range_intersects_region(lo, lo + (1 << (2 * BITS)),
                                            sp[:-1], sp[1:]).sum())
        queries = [
            [Pattern("?x", 101, "?y"), Pattern("?y", 102, "?z")],   # fat probe
            [Pattern("?x", 101, "?y"), Pattern("?y", 102, "?z"),
             Pattern("?y", 103, "?w")],                              # multiway
        ]
        ok, total = True, 0
        for pats in queries:
            want, ovars = execute_oracle(tr, pats)
            got = {}
            for routing in ("broadcast", "a2a"):
                caps = Caps(out_cap=1 << 13, probe_cap=512, row_cap=512,
                            bucket_cap=1024)
                t, v, ovf, vars_ = execute_sharded(store, pats, mesh,
                                                   "mapsin",
                                                   ExecConfig(routing=routing),
                                                   caps=caps)
                perm = [vars_.index(x) for x in ovars]
                got[routing] = {tuple(r[i] for i in perm)
                                for r in rows_set(t, v, len(vars_))}
                ok = ok and int(np.asarray(ovf).sum()) == 0
            ok = ok and got["a2a"] == got["broadcast"] == want
            total += len(want)
        print(json.dumps({"ok": ok, "spans": spans, "n": total}))
    """))
    assert res["spans"] >= 2, res
    assert res["ok"] and res["n"] > 0, res


def test_sharded_batched_serving_matches_local():
    """PR 4 tentpole: ServeEngine bound to an 8-device mesh executes each
    shape bucket as ONE shard_map dispatch (routing="a2a", auto-tuned
    buckets) against the region-sharded store — every batched result must
    be row-identical to execute_local, with batching actually happening
    (dispatches == number of templates, not of queries) and zero
    overflow."""
    res = run_in_subprocess(textwrap.dedent("""
        import json, numpy as np, jax
        from jax.sharding import Mesh
        from repro.core import (Caps, ExecConfig, Pattern, build_store,
                                execute_local, rows_set)
        from repro.serve import ServeEngine
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        rng = np.random.RandomState(5)
        tr = np.stack([rng.randint(0, 60, 800), rng.randint(100, 105, 800),
                       rng.randint(0, 60, 800)], 1).astype(np.int32)
        store = build_store(tr, num_shards=8)
        cfg = ExecConfig(routing="a2a")
        caps = Caps(out_cap=2048, probe_cap=64, row_cap=64)
        eng = ServeEngine(store, cfg=cfg, caps=caps, mesh=mesh, max_batch=8)
        queries = []
        for c in (1, 5, 9, 13, 17, 21):           # join template
            queries.append([Pattern("?x", 101, c), Pattern("?x", 102, "?y")])
        for c in (2, 7, 11):                      # bound-subject template
            queries.append([Pattern(c, 103, "?a"), Pattern("?a", 104, "?b")])
        for c in (3, 8):                          # multiway star template
            queries.append([Pattern("?x", 101, c), Pattern("?x", 102, "?a"),
                            Pattern("?x", 103, "?b")])
        results = eng.execute(queries)
        store1 = build_store(tr, 1)
        ok, n = True, 0
        for pats, r in zip(queries, results):
            bnd = execute_local(store1, pats, "mapsin", caps=caps)
            want = rows_set(bnd.table, bnd.valid, len(bnd.vars))
            ok = ok and r.rows_set(tuple(bnd.vars)) == want
            ok = ok and r.overflow == 0
            n += len(want)
        print(json.dumps({"ok": ok, "n": n, "dispatches": eng.dispatches,
                          "payload": eng.a2a_payload_bytes}))
    """))
    assert res["ok"] and res["n"] > 0, res
    assert res["dispatches"] == 3, res            # one per template
    assert res["payload"] > 0, res                # a2a traffic was accounted


def test_chaos_suite_8dev_faults_detected_rows_exact():
    """PR 6 chaos case at real shard count: a seeded FaultPlan injects
    drops and corruptions into the 8-shard a2a answer legs across the
    epoch schedule; the answer-leg checksums must detect every one, the
    dispatch loop must retry onto clean epochs, and every delivered row
    set must be bit-identical to execute_local — zero wrong rows under
    chaos. A saturated all-epochs-faulty plan must exhaust the retry
    budget with results flagged fault_unrecovered whose rows are a
    SUBSET of the truth (quarantined, not corrupted)."""
    res = run_in_subprocess(textwrap.dedent("""
        import json, numpy as np, jax
        from jax.sharding import Mesh
        from repro.core import (Caps, ExecConfig, Pattern, build_store,
                                execute_local, rows_set)
        from repro.serve import Fault, FaultPlan, ServeEngine
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        rng = np.random.RandomState(11)
        tr = np.stack([rng.randint(0, 60, 800), rng.randint(100, 105, 800),
                       rng.randint(0, 60, 800)], 1).astype(np.int32)
        store = build_store(tr, num_shards=8)
        store1 = build_store(tr, 1)
        cfg = ExecConfig(routing="a2a")
        caps = Caps(out_cap=2048, probe_cap=64, row_cap=64)
        queries = [[Pattern("?x", 101, c), Pattern("?x", 102, "?y")]
                   for c in (1, 5, 9, 13)]
        queries += [[Pattern("?x", 101, "?y"), Pattern("?y", 102, "?z")]]
        # seeded plan, high rate so several epochs are actually faulty
        fp = FaultPlan.sample(3, num_shards=8, n_steps=1, rate=0.10,
                              horizon=16)
        assert any(fp.at(e, 0) != ((), ()) for e in range(16))
        eng = ServeEngine(store, cfg=cfg, caps=caps, mesh=mesh,
                          fault_plan=fp, fault_retries=4)
        results = eng.execute(queries)
        ok = True
        for pats, r in zip(queries, results):
            bnd = execute_local(store1, pats, "mapsin", caps=caps)
            want = rows_set(bnd.table, bnd.valid, len(bnd.vars))
            ok = ok and r.rows_set(tuple(bnd.vars)) == want
            ok = ok and "fault_unrecovered" not in (r.stats or {})
        # saturated chaos: every epoch corrupts shard 2 -> unrecoverable,
        # surviving rows still a strict subset of the truth, never wrong
        sat = FaultPlan((Fault(0, 2, "corrupt", epoch=0),), period=1)
        eng2 = ServeEngine(store, cfg=cfg, caps=caps, mesh=mesh,
                           fault_plan=sat, fault_retries=2,
                           max_escalations=0)
        r2 = eng2.execute([queries[-1]])[0]
        bnd = execute_local(store1, queries[-1], "mapsin", caps=caps)
        want = rows_set(bnd.table, bnd.valid, len(bnd.vars))
        subset = r2.rows_set(tuple(bnd.vars)) <= want
        print(json.dumps({
            "ok": ok, "detected": eng.corrupt_detected,
            "redispatches": eng.fault_redispatches,
            "unrecovered_flagged": bool(
                (r2.stats or {}).get("fault_unrecovered")),
            "subset": subset, "sat_detected": eng2.corrupt_detected}))
    """))
    assert res["ok"], res                          # zero wrong rows
    assert res["detected"] > 0, res                # faults actually fired
    assert res["redispatches"] > 0, res            # and were retried
    assert res["unrecovered_flagged"], res
    assert res["subset"], res                      # quarantine, not corruption
    assert res["sat_detected"] >= 3, res


def test_sharded_train_step_matches_single_device():
    """2x4 mesh (data x model) train step == single-device train step."""
    res = run_in_subprocess(textwrap.dedent("""
        import json, numpy as np, jax, jax.numpy as jnp
        from repro.configs import get_config, reduce_for_smoke
        from repro.configs.base import ShapeConfig
        from repro.models import build_model, make_train_step, input_defs
        from repro.models.params import init_tree, pspec_tree
        from repro.optim import OptConfig, init_opt_state
        from repro.sharding.rules import make_rules
        from repro.launch.mesh import make_mesh_for

        cfg = reduce_for_smoke(get_config("qwen3-8b"))
        shape = ShapeConfig("t", 32, 8, "train")
        rng = np.random.RandomState(0)
        batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 32)), jnp.int32),
                 "labels": jnp.asarray(rng.randint(0, cfg.vocab_size, (8, 32)), jnp.int32)}
        opt = OptConfig()
        # single device
        m1 = build_model(cfg)
        p1 = init_tree(m1.param_defs(), jax.random.key(0))
        s1 = init_opt_state(p1, opt)
        q1, _, met1 = jax.jit(make_train_step(m1, opt))(p1, s1, batch)
        # 2x4 sharded
        mesh = make_mesh_for(8, model_par=4)
        rules = make_rules(mesh, cfg, shape)
        m2 = build_model(cfg, mesh, rules)
        p2 = init_tree(m2.param_defs(), jax.random.key(0))
        s2 = init_opt_state(p2, opt)
        with mesh:
            q2, _, met2 = jax.jit(make_train_step(m2, opt))(p2, s2, batch)
        dl = abs(float(met1["loss"]) - float(met2["loss"]))
        dp = max(float(np.max(np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))))
                 for a, b in zip(jax.tree.leaves(q1), jax.tree.leaves(q2)))
        print(json.dumps({"dloss": dl, "dparam": dp}))
    """))
    assert res["dloss"] < 1e-4, res
    assert res["dparam"] < 1e-2, res  # bf16 params, collective reduction order


def test_elastic_checkpoint_reshard():
    """Save on 1-device mesh, restore onto an 8-device mesh (and back)."""
    res = run_in_subprocess(textwrap.dedent("""
        import json, tempfile, numpy as np, jax, jax.numpy as jnp
        from repro.checkpoint import save, load, latest
        from repro.configs import get_config, reduce_for_smoke
        from repro.models import build_model
        from repro.models.params import init_tree, sharding_tree
        from repro.sharding.rules import make_rules
        from repro.launch.mesh import make_mesh_for

        cfg = reduce_for_smoke(get_config("yi-6b"))
        model = build_model(cfg)
        params = init_tree(model.param_defs(), jax.random.key(1))
        with tempfile.TemporaryDirectory() as d:
            save(d, 5, {"params": params})
            mesh = make_mesh_for(8, model_par=4)
            rules = make_rules(mesh, cfg)
            shardings = sharding_tree(build_model(cfg, mesh, rules).param_defs(), rules)
            step, out = load(latest(d), {"params": params},
                             {"params": shardings})
            ok = step == 5
            for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(out["params"])):
                ok = ok and bool(np.array_equal(np.asarray(a, np.float32),
                                                np.asarray(b, np.float32)))
                ok = ok and len(b.sharding.device_set) > 1
        print(json.dumps({"ok": ok}))
    """))
    assert res["ok"]


def test_mapsin_embedding_matches_dense():
    res = run_in_subprocess(textwrap.dedent("""
        import json, numpy as np, jax, jax.numpy as jnp
        from repro.models.embedding import dense_embed, mapsin_embed
        from repro.sharding.rules import make_rules
        from repro.launch.mesh import make_mesh_for
        mesh = make_mesh_for(8, model_par=8)
        rules = make_rules(mesh)
        rng = np.random.RandomState(0)
        table = jnp.asarray(rng.randn(64, 16), jnp.float32)
        toks = jnp.asarray(rng.randint(0, 64, (4, 10)), jnp.int32)
        with mesh:
            got = jax.jit(lambda t, x: mapsin_embed(t, x, mesh, rules))(table, toks)
        want = dense_embed(table, toks)
        err = float(jnp.max(jnp.abs(got - want)))
        print(json.dumps({"err": err}))
    """))
    assert res["err"] < 1e-6
