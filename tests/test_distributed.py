"""Distribution correctness on multi-device CPU meshes (subprocess-isolated
because XLA fixes the host device count per process).

Covers: sharded-vs-single-device train-step parity, the distributed VDT LP
step vs the reference matvec, and the pod-axis pipeline schedule.
"""
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run(body: str, n_dev: int = 8, timeout: int = 420) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_dev}"
        import sys
        sys.path.insert(0, {SRC!r})
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
    """) + textwrap.dedent(body)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    """One train step on a 4x2 mesh must match the unsharded step."""
    _run("""
        from repro.configs.registry import get_smoke_config
        from repro.distributed.sharding import ShardCtx, param_shardings, use_ctx
        from repro.models.transformer import init_lm
        from repro.training.optimizer import AdamWConfig
        from repro.training.train_step import init_train_state, make_train_step

        cfg = get_smoke_config("internlm2-1.8b")
        opt = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
        params = init_lm(cfg, jax.random.PRNGKey(0))
        state = init_train_state(params, opt)
        r = np.random.RandomState(0)
        batch = {"tokens": jnp.asarray(
            r.randint(0, cfg.vocab_size, (8, 33)), jnp.int32)}
        step = make_train_step(cfg, opt)

        # single-logical-device reference
        s1, m1 = jax.jit(step)(state, batch)

        # sharded: FSDP over data(4) x TP over model(2)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        ctx = ShardCtx(mesh=mesh, dp=("data",))
        ps = param_shardings(params, ctx)
        st_sh = type(state)(params=ps,
                            opt=type(state.opt)(step=NamedSharding(mesh, P()),
                                                mu=ps, nu=ps),
                            step=NamedSharding(mesh, P()))
        bt_sh = {"tokens": NamedSharding(mesh, P("data", None))}

        def fn(s, b):
            with use_ctx(ctx):
                return step(s, b)

        with mesh:
            s2, m2 = jax.jit(fn, in_shardings=(st_sh, bt_sh))(state, batch)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 5e-2, (
            float(m1["loss"]), float(m2["loss"]))
        # parameters after update agree
        l1 = jax.tree_util.tree_leaves(s1.params)
        l2 = jax.tree_util.tree_leaves(s2.params)
        worst = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32))))
                    for a, b in zip(l1, l2))
        assert worst < 5e-2, worst
        print("PARITY OK", float(m1["loss"]), worst)
    """)


def test_distributed_vdt_lp_step_matches_reference():
    """The sharded paper_vdt LP step == the single-device block matvec."""
    _run("""
        from repro.core.distributed import lp_step_leaforder
        from repro.core.tree import build_tree
        from repro.core.blocks import coarsest_partition
        from repro.core.qopt import optimize_q
        from repro.core.matvec import mpt_matvec_leaforder, scan_table

        r = np.random.RandomState(0)
        n, d, c = 1024, 8, 4
        x = r.randn(n, d).astype(np.float32)
        tree = build_tree(x)
        bp = coarsest_partition(tree)
        qs = optimize_q(tree, jnp.asarray(bp.a), jnp.asarray(bp.b),
                        jnp.asarray(bp.active), jnp.asarray(1.0))
        q = jnp.where(jnp.isfinite(qs.log_q), jnp.exp(qs.log_q), 0.0)
        y = jnp.asarray(r.randn(n, c), jnp.float32)
        y0 = jnp.asarray(r.randn(n, c), jnp.float32)
        alpha = 0.3

        ta, tb, tq = scan_table(bp.a, bp.b, bp.active, q, tree.n_nodes)
        assert (np.diff(ta) >= 0).all()  # the segment-sum takes them as sorted
        ref = alpha * mpt_matvec_leaforder(y, ta, tb, tq, tree.L) \\
              + (1 - alpha) * y0

        # pad blocks to a shard-divisible count with inert q=0 entries
        nb = bp.a.shape[0]
        pad = (-nb) % 8
        a = jnp.pad(jnp.asarray(bp.a), (0, pad))
        b = jnp.pad(jnp.asarray(bp.b), (0, pad))
        qq = jnp.pad(q, (0, pad))

        mesh = jax.make_mesh((8,), ("data",))
        sh_rows = NamedSharding(mesh, P("data", None))
        sh_blocks = NamedSharding(mesh, P("data"))
        with mesh:
            got = jax.jit(
                lambda yl, y0l, aa, bb, qv: lp_step_leaforder(
                    yl, y0l, aa, bb, qv, alpha, tree.L),
                in_shardings=(sh_rows, sh_rows, sh_blocks, sh_blocks,
                              sh_blocks),
            )(y, y0, a, b, qq)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)
        print("VDT DIST OK")
    """)


def test_pipeline_matches_sequential():
    """GPipe over a 4-stage pod axis == running stages sequentially."""
    _run("""
        from repro.distributed.pipeline import pipeline_forward

        n_stages, n_micro, mb, dim = 4, 8, 2, 16
        r = np.random.RandomState(0)
        ws = jnp.asarray(r.randn(n_stages, dim, dim) * 0.3, jnp.float32)
        x = jnp.asarray(r.randn(n_micro, mb, dim), jnp.float32)

        def stage_fn(w, h, stage_idx):
            return jnp.tanh(h @ w)

        # sequential reference
        ref = x
        for s in range(n_stages):
            ref = jnp.tanh(ref @ ws[s])

        mesh = jax.make_mesh((4,), ("pod",))
        with mesh:
            got = pipeline_forward(stage_fn, ws, x, mesh, axis="pod")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)
        print("PIPELINE OK")
    """, n_dev=4)
