"""Sharded multi-device serving engine: the same scheduler, SPMD math.

:class:`ShardedPropagateEngine` is the second concrete implementation of
the :class:`~repro.serving.engine_api.Engine` contract.  It subclasses
:class:`~repro.serving.PropagateEngine` and overrides exactly the two
device-math hooks (``_scan`` / ``_scan_resume``), so the entire
scheduler — queue disciplines, width/batch bucketing, segmented EDF
preemption, epoch pinning, refcounted retirement, metrics — is inherited
verbatim and every dispatch runs SPMD over a 1-D device mesh instead.

Data placement (``distributed/sharding.py::leaf_mesh`` / ``leaf_sharding``)
---------------------------------------------------------------------------
Leaf-order arrays — the scattered label stack ``(n_leaves, K)`` and the
ghost-leaf mask — are row-sharded over the ``"leaves"`` mesh axis with a
``NamedSharding``; the (small) block lists ``a``/``b``, the q weights and
the per-column alpha row are replicated.  Both scans are ``shard_map``
bodies wrapped in ``jit`` with explicit input/output shardings, so device
placement is part of the compiled executable, not a runtime reshard.

Bit parity with the single-device engine
----------------------------------------
The serving contract is *bit* parity, not tolerance parity, and it is met
by construction:

* **VDT backend** — a power-of-two device count D = 2^k makes every
  device own one aligned depth-(L-k) subtree of the perfect partition
  tree.  CollectUp runs locally per subtree (the identical pairwise
  summation tree), ONE all-gather shares the per-shard partial trees, and
  the top k levels are summed from the gathered subtree roots — again the
  identical pairwise adds, pinned against XLA re-association by the
  ``optimization_barrier`` inside :func:`~repro.core.matvec.collect_up`.
  The per-block contraction ``c = q * T[b]`` + segment-sum is computed
  replicated (it is O(|B|), tiny, and identical on every device — no psum
  anywhere), and DistributeDown walks the replicated top-k prefix then
  slices into the device's own subtree.  Every float add happens in the
  same order as the single-device program.
* **Exact backend** — rows of the streamed transition matrix are
  independent, so each device runs the fused Pallas kernel over its own
  row stripe against the full column space (one all-gather of the folded
  carry per iteration).  ALL tile sizes are kept identical to the
  single-device kernel: the column tiling (``block_n``, padded size
  ``sp``) determines each row's online-softmax association order, and
  the row-block size ``block_m`` selects the matmul lowering for the
  ``p @ y`` contraction (a smaller M measurably changes bits for some
  widths).  Each device's stripe is therefore padded *locally* up to the
  256-row tile — the blocked layout — and the pad rows' outputs are
  simply discarded.  The stripe's global row offset rides into the
  kernel (``row_base``) so the self-transition diagonal masks the same
  entries it does in the whole-matrix grid.

Both resume twins use a dynamic ``fori_loop`` bound exactly like the
single-device engine, so segmented EDF preemption re-enters the very same
per-iteration program and the PR-6 carry guarantee (pause/resume is
bit-identical to never pausing) holds across the mesh.

CPU story: ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (set
before importing jax) makes all of this testable on one CI host; with a
single visible device the engine degenerates to a 1-device mesh and still
exercises the full SPMD code path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.label_prop import column_slabs
from repro.core.matvec import collect_up, fold_batch, unfold_batch
from repro.distributed.sharding import LEAF_AXIS, leaf_mesh, leaf_sharding
from repro.kernels import interpret_mode
from repro.serving._engine import PropagateEngine

__all__ = ["ShardedPropagateEngine"]

_BLOCK = 256  # exact-kernel tile (rows AND cols); MUST match single-device


def _to_blocked(y, D: int, rps: int, mp_loc: int, pad_value=0.0):
    """``(D*rps, k) -> (D*mp_loc, k)``: pad each device's ``rps``-row
    stripe up to the ``mp_loc`` row tile so a row-sharded array hands every
    device a whole number of 256-row kernel blocks.  Identity when the
    stripe already tiles evenly."""
    if mp_loc == rps:
        return y
    y = y.reshape(D, rps, y.shape[-1])
    y = jnp.pad(y, ((0, 0), (0, mp_loc - rps), (0, 0)),
                constant_values=pad_value)
    return y.reshape(D * mp_loc, y.shape[-1])


def _from_blocked(y, D: int, rps: int, mp_loc: int):
    """Inverse of :func:`_to_blocked`: drop each stripe's local pad rows."""
    if mp_loc == rps:
        return y
    return y.reshape(D, mp_loc, y.shape[-1])[:, :rps].reshape(
        D * rps, y.shape[-1])


def _sharded_matvec(y_sh, a, b, q, *, L: int, K: int, axis: str):
    """Per-shard Algorithm-1 matvec: local CollectUp, one all-gather,
    replicated block contraction, subtree DistributeDown.

    ``y_sh`` is this device's ``(n_leaves/D, C)`` leaf stripe; returns the
    matching stripe of (QY).  ``K = log2(D)``; levels ``0..K`` of the tree
    are computed/walked replicated, levels below live shard-local.
    """
    Lloc = L - K
    t_loc = collect_up(y_sh, Lloc)                 # (2*Nl - 1, C) local tree
    if K == 0:
        t_full = t_loc
    else:
        t_all = jax.lax.all_gather(t_loc, axis)    # (D, 2*Nl - 1, C)
        # subtree roots are the full tree's level-K nodes; summing them up
        # reproduces levels 0..K with the same pairwise adds
        top = collect_up(t_all[:, 0, :], K)        # (2D - 1, C)
        parts = [top]
        for j in range(1, Lloc + 1):
            lo, hi = (1 << j) - 1, (1 << (j + 1)) - 1
            parts.append(t_all[:, lo:hi, :].reshape(-1, t_all.shape[-1]))
        t_full = jnp.concatenate(parts, axis=0)    # (n_nodes, C) level-major
    n_nodes = (1 << (L + 1)) - 1
    # per-block contraction + segment-sum over the scan table (sorted row
    # nodes, as the single-device matvec takes them): O(|B| C), replicated
    # — every device computes the identical c_node, so no psum is needed
    c_block = q[:, None] * jnp.take(t_full, b, axis=0)
    c_node = jax.ops.segment_sum(c_block, a, num_segments=n_nodes,
                                 indices_are_sorted=True)
    # DistributeDown: replicated down to level K, then into our subtree
    acc = c_node[0:1, :]
    d = jax.lax.axis_index(axis)
    for lvl in range(L):
        lo, hi = (1 << (lvl + 1)) - 1, (1 << (lvl + 2)) - 1
        if lvl < K:
            acc = jnp.repeat(acc, 2, axis=0) + c_node[lo:hi, :]
            if lvl == K - 1:
                acc = jax.lax.dynamic_slice_in_dim(acc, d, 1, axis=0)
        else:
            width = 1 << (lvl + 1 - K)
            mine = jax.lax.dynamic_slice_in_dim(
                c_node[lo:hi, :], d * width, width, axis=0)
            acc = jnp.repeat(acc, 2, axis=0) + mine
    return acc


class ShardedPropagateEngine(PropagateEngine):
    """Multi-device SPMD :class:`~repro.serving.PropagateEngine`.

    Same constructor surface as the single-device engine plus ``devices``
    (default: all visible devices; must be a power-of-two count).  The grf
    walker backend is not served — its complete kernel graph is dense and
    does not shard along leaves — so ``capabilities()`` reports
    ``{"publish", "sharded"}`` (plus ``"preempt"`` under the EDF/segmented
    configuration) and grf submits are rejected at the call site.
    """

    def __init__(self, vdt, *, devices=None, **kwargs):
        if kwargs.get("backend") == "grf":
            raise ValueError(
                "ShardedPropagateEngine does not serve backend='grf' "
                "(the walker estimator's kernel graph does not shard "
                "along leaves); use PropagateEngine")
        self._mesh = leaf_mesh(devices)
        self._axis = LEAF_AXIS
        self.n_devices = int(self._mesh.shape[LEAF_AXIS])
        if self.n_devices > _BLOCK:
            raise ValueError(
                f"ShardedPropagateEngine supports at most {_BLOCK} "
                f"devices (row-striping granularity of the exact "
                f"kernel), got {self.n_devices}")
        self._K = self.n_devices.bit_length() - 1
        self._row_sharding = leaf_sharding(self._mesh)
        self._rep_sharding = NamedSharding(self._mesh, P())
        # jitted SPMD executables keyed on their closure statics; jax.jit
        # handles per-shape caching underneath each entry
        self._jit_cache: dict = {}
        # per-epoch device buffers keyed id(vdt) — the epoch record pins
        # the tree, and _retire_locked() drops our entry with it
        self._buf_cache: dict[int, dict] = {}
        self._check_model(vdt)
        super().__init__(vdt, **kwargs)

    # ----------------------------------------------------- introspection
    def capabilities(self) -> frozenset[str]:
        """Publish/preempt as configured, ``"sharded"``, never ``"grf"``."""
        return (super().capabilities() - {"grf"}) | {"sharded"}

    # --------------------------------------------------------- lifecycle
    def _check_model(self, vdt) -> None:
        n_leaves = int(vdt.tree.n_leaves)
        if self.n_devices > n_leaves:
            raise ValueError(
                f"cannot shard a {n_leaves}-leaf tree over "
                f"{self.n_devices} devices: each device must own at "
                f"least one leaf")

    def publish(self, model, *, patched_points: int = 0,
                stale_blocks: int = 0) -> int:
        """Epoch swap with the inherited atomicity contract; the new tree
        must still divide over the mesh (collective only in the sense that
        later dispatches against the new epoch are; the swap itself is a
        host-side pointer swap exactly like the base engine's)."""
        self._check_model(model)
        return super().publish(model, patched_points=patched_points,
                               stale_blocks=stale_blocks)

    def _retire_locked(self) -> None:
        super()._retire_locked()
        live = {id(ep.vdt) for ep in self._epochs.values()}
        live.add(id(self.vdt))
        for key in [k for k in self._buf_cache if k not in live]:
            del self._buf_cache[key]

    # --------------------------------------------------- per-epoch buffers
    def _buffers(self, vdt) -> dict:
        buf = self._buf_cache.get(id(vdt))
        if buf is None:
            a, b, q, mask = vdt._dispatch_buffers()
            tree = vdt.tree
            # place once per epoch: block lists / q replicated over the
            # mesh, the ghost mask row-sharded with the label stripes
            rep, row = self._rep_sharding, self._row_sharding
            buf = {"L": int(tree.L), "n_leaves": int(tree.n_leaves),
                   "slot_of": tree.slot_of,
                   "a": jax.device_put(a, rep), "b": jax.device_put(b, rep),
                   "q": jax.device_put(q, rep),
                   "mask": jax.device_put(mask, row)}
            self._buf_cache[id(vdt)] = buf
        return buf

    def _exact_buffers(self, vdt) -> dict:
        buf = self._buffers(vdt)
        if "xp" not in buf:
            # deferred so constructing the engine never pulls the Pallas
            # toolchain unless the exact backend is actually dispatched
            from repro.core.divergence import resolve_divergence
            from repro.kernels.fused_lp.fused_lp import tile_config

            div = resolve_divergence(vdt.bound_divergence.div)
            tile_fn, pad, transform = tile_config(div)
            xr = vdt.x_rows
            if transform is not None:
                xr = transform(xr)
            n = int(xr.shape[0])
            # identical column padding to the single-device fused scan:
            # sp is part of each row's online-softmax association order
            sp = -(-n // _BLOCK) * _BLOCK
            D = self.n_devices
            rps = sp // D                       # rows per shard (stripe)
            mp_loc = -(-rps // _BLOCK) * _BLOCK  # stripe padded to row tile
            xp = jnp.pad(xr, ((0, sp - n), (0, 0)), constant_values=pad)
            # the padded points enter the scan twice: as each device's own
            # blocked row stripe and as the replicated column set
            buf["xp_row"] = jax.device_put(
                _to_blocked(xp, D, rps, mp_loc, pad_value=pad),
                self._row_sharding)
            buf["xp_rep"] = jax.device_put(xp, self._rep_sharding)
            buf["sp"] = sp
            buf["rps"] = rps
            buf["mp_loc"] = mp_loc
            buf["n_valid"] = n
            buf["div_name"] = div.name
            buf["tile_fn"] = tile_fn
            buf["inv"] = float(
                1.0 / (2.0 * float(vdt.sigma) * float(vdt.sigma)))
        return buf

    # ------------------------------------------------- jitted SPMD scans
    def _jit_sharded(self, body, n_sharded: int, n_rep: int):
        """``shard_map`` + ``jit`` with explicit input/output shardings:
        the first ``n_sharded`` args row-sharded over leaves, the rest
        replicated; the result row-sharded."""
        row = P(self._axis, None)
        mapped = jax.shard_map(
            body, mesh=self._mesh,
            in_specs=tuple([row] * n_sharded + [P()] * n_rep),
            out_specs=row, check_vma=False)
        return jax.jit(
            mapped,
            in_shardings=tuple([self._row_sharding] * n_sharded
                               + [self._rep_sharding] * n_rep),
            out_shardings=self._row_sharding)

    def _vdt_scan(self, L: int, n_iters: int):
        key = ("vdt", L, int(n_iters))
        fn = self._jit_cache.get(key)
        if fn is None:
            K, axis = self._K, self._axis

            def body(y0_sh, mask_sh, a, b, q, alpha):
                def walk(y0_sh, alpha):
                    def step(y, _):
                        y = mask_sh * (alpha * _sharded_matvec(
                            y, a, b, q, L=L, K=K, axis=axis)) \
                            + (1.0 - alpha) * y0_sh
                        return y, None
                    y, _ = jax.lax.scan(step, y0_sh, None,
                                        length=int(n_iters))
                    return y
                # the single-device scan's column slabs, slab for slab
                return column_slabs(walk, (y0_sh,), alpha)

            fn = self._jit_sharded(body, n_sharded=2, n_rep=4)
            self._jit_cache[key] = fn
        return fn

    def _vdt_resume(self, L: int):
        key = ("vdt_resume", L)
        fn = self._jit_cache.get(key)
        if fn is None:
            K, axis = self._K, self._axis

            # n_it is a dynamic fori_loop bound, mirroring the
            # single-device resume: one executable per shape covers every
            # segment length the scheduler can slice
            def body(y_sh, y0_sh, mask_sh, a, b, q, alpha, n_it):
                def walk(y_sh, y0_sh, alpha):
                    def it(_, y):
                        return mask_sh * (alpha * _sharded_matvec(
                            y, a, b, q, L=L, K=K, axis=axis)) \
                            + (1.0 - alpha) * y0_sh
                    return jax.lax.fori_loop(0, n_it, it, y_sh)
                return column_slabs(walk, (y_sh, y0_sh), alpha)

            fn = self._jit_sharded(body, n_sharded=3, n_rep=5)
            self._jit_cache[key] = fn
        return fn

    def _exact_body(self, buf: dict):
        """One fused eq.-15 step over this device's blocked row stripe.

        The per-device carry is the ``(mp_loc, K)`` blocked stripe; each
        step all-gathers the stripes' REAL rows back into the full
        ``(sp, K)`` folded carry (bitwise the single-device carry,
        including the mid-scan epilogue garbage on global pad rows) and
        runs the kernel with the very same 256x256 tiles the single-device
        scan uses — only the row grid is shorter."""
        axis = self._axis
        n_valid, inv = buf["n_valid"], buf["inv"]
        rps, tile_fn = buf["rps"], buf["tile_fn"]
        interpret = interpret_mode()
        from repro.kernels.fused_lp.batched import _folded_call

        def step(x_rows, x_full, y_sh, y0_sh, al, row_base):
            y_full = jax.lax.all_gather(y_sh[:rps], axis, axis=0, tiled=True)
            return _folded_call(
                x_rows, x_full, y_full, y0_sh, al,
                inv_two_sigma_sq=inv, n_valid=n_valid,
                block_m=_BLOCK, block_n=_BLOCK,
                interpret=interpret, tile_fn=tile_fn, row_base=row_base)

        return step

    def _exact_scan(self, buf: dict, n_iters: int):
        key = ("exact", buf["sp"], buf["n_valid"], buf["inv"],
               buf["div_name"], int(n_iters))
        fn = self._jit_cache.get(key)
        if fn is None:
            axis, rps = self._axis, buf["rps"]
            one = self._exact_body(buf)

            def body(x_rows, y0_sh, x_full, al):
                rb = jax.lax.axis_index(axis) * rps

                def step(y_sh, _):
                    return one(x_rows, x_full, y_sh, y0_sh, al, rb), None
                y, _ = jax.lax.scan(step, y0_sh, None, length=int(n_iters))
                return y

            fn = self._jit_sharded(body, n_sharded=2, n_rep=2)
            self._jit_cache[key] = fn
        return fn

    def _exact_resume(self, buf: dict):
        key = ("exact_resume", buf["sp"], buf["n_valid"], buf["inv"],
               buf["div_name"])
        fn = self._jit_cache.get(key)
        if fn is None:
            axis, rps = self._axis, buf["rps"]
            one = self._exact_body(buf)

            def body(y_sh, y0_sh, x_rows, x_full, al, n_it):
                rb = jax.lax.axis_index(axis) * rps
                return jax.lax.fori_loop(
                    0, n_it,
                    lambda _, y: one(x_rows, x_full, y, y0_sh, al, rb),
                    y_sh)

            fn = self._jit_sharded(body, n_sharded=3, n_rep=3)
            self._jit_cache[key] = fn
        return fn

    # ------------------------------------------------- device-math hooks
    @staticmethod
    def _fold(stack, alphas):
        y0 = jnp.asarray(stack)
        if not jnp.issubdtype(y0.dtype, jnp.floating):
            y0 = y0.astype(jnp.float32)
        bb, _, cb = y0.shape
        alpha = jnp.repeat(jnp.asarray(alphas, jnp.float32), cb)
        return fold_batch(y0), alpha, bb, cb

    def _scan(self, vdt, stack, alphas, n_iters: int, backend: str, *,
              n_walkers=None):
        if backend == "grf":
            raise ValueError(
                "ShardedPropagateEngine does not serve backend='grf'")
        y, alpha, bb, cb = self._fold(stack, alphas)
        row, rep = self._row_sharding, self._rep_sharding
        alpha = jax.device_put(alpha, rep)
        if backend == "vdt":
            buf = self._buffers(vdt)
            y_leaf = jnp.zeros((buf["n_leaves"], y.shape[1]), y.dtype)
            y_leaf = jax.device_put(y_leaf.at[buf["slot_of"]].set(y), row)
            out_leaf = self._vdt_scan(buf["L"], n_iters)(
                y_leaf, buf["mask"], buf["a"], buf["b"], buf["q"], alpha)
            out = out_leaf[buf["slot_of"]]
        else:
            buf = self._exact_buffers(vdt)
            sp, n = buf["sp"], buf["n_valid"]
            D, rps, mp_loc = self.n_devices, buf["rps"], buf["mp_loc"]
            y0p = jnp.pad(y, ((0, sp - n), (0, 0)))
            y0b = jax.device_put(_to_blocked(y0p, D, rps, mp_loc), row)
            al = jax.device_put(_alpha_row(alpha, y.shape[1]), rep)
            fn = self._exact_scan(buf, n_iters)
            out_b = fn(buf["xp_row"], y0b, buf["xp_rep"], al)
            out = _from_blocked(out_b, D, rps, mp_loc)[:n]
        return unfold_batch(out, bb, cb)

    def _scan_resume(self, vdt, carry, y0, alphas, n_iters, backend: str):
        if backend == "grf":
            raise ValueError(
                "backend='grf' does not support segmented resume")
        yc, alpha, bb, cb = self._fold(carry, alphas)
        ys, _, _, _ = self._fold(y0, alphas)
        row, rep = self._row_sharding, self._rep_sharding
        alpha = jax.device_put(alpha, rep)
        n_it = jax.device_put(jnp.asarray(int(n_iters), jnp.int32), rep)
        if backend == "vdt":
            buf = self._buffers(vdt)
            z = jnp.zeros((buf["n_leaves"], yc.shape[1]), yc.dtype)
            c_leaf = jax.device_put(z.at[buf["slot_of"]].set(yc), row)
            y0_leaf = jax.device_put(z.at[buf["slot_of"]].set(ys), row)
            out_leaf = self._vdt_resume(buf["L"])(
                c_leaf, y0_leaf, buf["mask"], buf["a"], buf["b"],
                buf["q"], alpha, n_it)
            out = out_leaf[buf["slot_of"]]
        else:
            buf = self._exact_buffers(vdt)
            sp, n = buf["sp"], buf["n_valid"]
            D, rps, mp_loc = self.n_devices, buf["rps"], buf["mp_loc"]
            # re-padding the carry with zeros between segments is safe:
            # the kernel's column mask keeps pad rows out of every
            # accumulation (same invariant as the single-device resume)
            ycb = jax.device_put(_to_blocked(
                jnp.pad(yc, ((0, sp - n), (0, 0))), D, rps, mp_loc), row)
            ysb = jax.device_put(_to_blocked(
                jnp.pad(ys, ((0, sp - n), (0, 0))), D, rps, mp_loc), row)
            al = jax.device_put(_alpha_row(alpha, yc.shape[1]), rep)
            fn = self._exact_resume(buf)
            out_b = fn(ycb, ysb, buf["xp_row"], buf["xp_rep"], al, n_it)
            out = _from_blocked(out_b, D, rps, mp_loc)[:n]
        return unfold_batch(out, bb, cb)


def _alpha_row(alpha, k: int):
    from repro.kernels.fused_lp.batched import _alpha_row as _ar

    return _ar(alpha, k)
