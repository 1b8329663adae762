"""Batched client execution: one compiled program per COLLECT wave.

The sequential path trains a round's finishers one Python-synchronous jit
step at a time — the hardware never sees the parallelism the simulator
models.  :class:`BatchedExecutor` runs an entire *wave* of clients' local
training as ONE compiled program:

* **dense** — every client in the wave has the same batch shape: ``vmap``
  over a client axis.  Per-client params trajectories, optimizer states
  and RNG streams (``seed = round*1000 + cid``, folded per step) ride the
  same ``lax.scan`` over local steps.  When a mesh is present the wave is
  wrapped in ``shard_map`` with the ``repro.dist`` logical-axis rules
  (``"clients"`` → the batch axes), so the client axis physically spreads
  over devices.
* **ragged** — clients have *different* per-step batch sizes (MLP kind):
  each step's examples are concatenated into one row block sorted by
  client, and every dense layer becomes a ``grouped_matmul`` with
  clients as the groups and per-client row counts as the group sizes —
  exactly how the kernel handles MoE expert groups.  ``group_sizes`` and
  the row→client segment ids are *traced* arguments, so one compiled
  program serves every wave with the same (clients, steps, rows, width)
  envelope regardless of how the rows split across clients.  Zero-row
  clients are legal (their loss, metrics and delta are exactly zero).
* **sequential fallback** — single-client waves (bit-identical to the
  sequential path by construction), non-MLP ragged waves, and anything
  else the batched paths cannot express run the cached
  ``make_small_step`` per client, consuming the exact same data-pipeline
  state as ``FLClient.train_local`` would.

Batches are pulled from each client's ``ClientDataset`` *in client order
before execution*, which advances the per-client shuffling RNG exactly as
the sequential loop does — so batched and sequential runs see identical
data.  Within one compiled wave the per-client updates are mathematically
the per-client sequential updates; summation order inside matmuls differs,
so cross-path comparisons are allclose (documented in
docs/architecture.md § batched executor), while the single-client
fallback stays bit-identical.

The wave hands its deltas over on the device: one compiled unstack turns
the stacked outputs into one ``jax.Array`` tree per client, and only the
per-client metrics cross to the host.  The FedAvg fold
(``repro.core.aggregation.fedavg``) then sums them where they are.

Compiled wave programs are cached on the wave *envelope* (mode, client
count, steps, batch geometry, dtypes); :class:`WaveStats` counts hits,
misses and fallbacks, mirrored onto the obs plane as the
``client.batch_*`` counters.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.aggregation import tree_sub
from repro.fed.client import build_step_fn, make_small_step
from repro.kernels.grouped_matmul.ops import grouped_matmul
from repro.models.small import SmallModelConfig
from repro.obs.metrics import Counter
from repro.obs.trace import span
from repro.optim.optimizers import Optimizer, clip_by_global_norm

PyTree = Any

#: default logical→physical rule for the wave's client axis: clients are
#: data parallelism, so the wave spreads over the batch axes.
DEFAULT_CLIENT_RULES: Dict[str, Tuple[str, ...]] = {"clients": ("pod", "data")}


@dataclass
class WaveStats:
    """Cumulative executor accounting (also mirrored to obs counters)."""

    waves: int = 0            # run_wave calls
    clients: int = 0          # clients that entered any wave
    dense_clients: int = 0    # trained through the vmap path
    ragged_clients: int = 0   # trained through the grouped_matmul path
    seq_clients: int = 0      # fell back to the sequential path
    compiles: int = 0         # wave-program cache misses
    cache_hits: int = 0       # wave-program cache hits

    def as_dict(self) -> Dict[str, int]:
        return {k: getattr(self, k) for k in (
            "waves", "clients", "dense_clients", "ragged_clients",
            "seq_clients", "compiles", "cache_hits")}


def _client_seed_keys(round_idx: int, cids) -> np.ndarray:
    """Per-client RNG stream roots: ``seed = round*1000 + cid`` — the same
    derivation the compression path uses, so every per-client stochastic
    choice in the stack hangs off one seed.  Built directly as uint32
    (hi, lo) words: one ``jax.random.PRNGKey`` dispatch per client would
    cost more than the whole compiled wave."""
    seeds = np.asarray([round_idx * 1000 + int(c) for c in cids], np.uint64)
    return np.stack([(seeds >> np.uint64(32)).astype(np.uint32),
                     (seeds & np.uint64(0xFFFFFFFF)).astype(np.uint32)], axis=1)


class BatchedExecutor:
    """Runs waves of clients' local training as single compiled programs.

    Parameters mirror what the sequential path derives from ``FedConfig``:
    the model config, the (cacheable) optimizer and the FedProx ``prox_mu``.
    ``mesh``/``rules`` opt the dense path into ``shard_map`` over the
    client axis; ``gmm_impl`` selects the grouped-matmul backend for the
    ragged path (``"ragged"`` = ``lax.ragged_dot``, ``"pallas"`` = the TPU
    kernel, interpreted only on the CPU backend, ``"dense"`` = masked
    dense matmul).
    The default is backend-aware: ``lax.ragged_dot`` lowers to a slow
    per-group loop on CPU where the masked-dense formulation is ~3x
    faster at FL-client sizes, so CPU defaults to ``"dense"`` and
    accelerators to ``"ragged"``.
    """

    def __init__(
        self,
        mcfg: SmallModelConfig,
        opt: Optimizer,
        prox_mu: float = 0.0,
        *,
        gmm_impl: Optional[str] = None,
        mesh=None,
        rules: Optional[dict] = None,
        obs=None,
        tenant: str = "batch",
    ):
        self.mcfg = mcfg
        self.opt = opt
        self.prox_mu = float(prox_mu)
        self.gmm_impl = gmm_impl or (
            "dense" if jax.default_backend() == "cpu" else "ragged")
        self.mesh = mesh
        self.rules = rules
        self.stats = WaveStats()
        self._compiled: Dict[tuple, Callable] = {}
        self.last_wave: Dict[str, Any] = {}
        reg = obs.registry if obs is not None else None
        self._c_waves = reg.counter("client.batch_waves", tenant) if reg else Counter()
        self._c_clients = reg.counter("client.batch_clients", tenant) if reg else Counter()
        self._c_compiles = reg.counter("client.batch_compiles", tenant) if reg else Counter()
        self._c_fallbacks = reg.counter("client.batch_fallbacks", tenant) if reg else Counter()
        self._c_h2d = reg.counter("client.h2d_bytes", tenant) if reg else Counter()
        self._c_d2h = reg.counter("client.d2h_bytes", tenant) if reg else Counter()
        self._trace = obs.tracer if obs is not None and obs.tracer.enabled else None
        self._tenant = tenant

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    def run_wave(
        self,
        global_params: PyTree,
        clients: Sequence[Any],
        n_steps: int,
        round_idx: int = 0,
    ) -> List[Tuple[PyTree, float, Dict[str, float]]]:
        """Train every client in ``clients`` for ``n_steps`` local steps
        from ``global_params``; returns ``(delta, n_seen, metrics)`` per
        client, in client order — the exact contract of
        ``FLClient.train_local`` looped sequentially.  The deltas are
        device arrays."""
        if not clients:
            return []
        self.stats.waves += 1
        self._c_waves.inc()
        self.stats.clients += len(clients)
        self._c_clients.inc(len(clients))
        with span("wave.prepare", self._trace, self._tenant, "train",
                  clients=len(clients)) as sp:
            # pull every client's batches up front, in client order —
            # consumes each ClientDataset's shuffle RNG exactly as the
            # sequential loop
            pulled = [list(c.data.batches(n_steps)) for c in clients]
            mode = ("seq" if len(clients) == 1 or n_steps <= 0
                    else self._pick_mode(pulled))
            sp.set(mode=mode)
            self.last_wave = {"mode": mode, "clients": len(clients),
                              "cache_hit": None}
            if mode == "dense":
                fn, host = self._prepare_dense(clients, pulled, round_idx)
            elif mode == "ragged":
                fn, host = self._prepare_ragged(clients, pulled, round_idx)
        if mode == "seq":
            self.stats.seq_clients += len(clients)
            self._c_fallbacks.inc(len(clients))
            return [self._run_sequential(global_params, c, bl)
                    for c, bl in zip(clients, pulled)]
        if mode == "dense":
            self.stats.dense_clients += len(clients)
        else:
            self.stats.ragged_clients += len(clients)
        # the compiled call moves the host arrays to the device
        h2d = sum(a.nbytes for a in host)
        self._c_h2d.inc(h2d)
        with span("wave.launch", self._trace, self._tenant, "train",
                  rows=sum(bl[0]["x"].shape[0] for bl in pulled), h2d_bytes=h2d):
            deltas, metrics = fn(global_params, *host)
        return self._split(deltas, metrics, clients, pulled)

    # ------------------------------------------------------------------
    # mode selection
    # ------------------------------------------------------------------

    def _pick_mode(self, pulled) -> str:
        # dtype objects hash fine — stringifying per batch costs more than
        # the whole mode decision on a 64x25 wave
        shapes = set()
        for bl in pulled:
            x0 = np.asarray(bl[0]["x"])
            sig = (x0.shape, x0.dtype, bl[0]["y"].shape)
            for b in bl[1:]:
                if (b["x"].shape, np.asarray(b["x"]).dtype, b["y"].shape) != sig:
                    return "seq"  # batch geometry varies across a client's steps
            shapes.add(sig)
        if len(shapes) == 1 and pulled[0][0]["x"].shape[0] > 0:
            return "dense"
        # ragged: MLP rows flatten to one feature width; clients become
        # grouped_matmul groups.  The personalization tower ("local") and
        # conv/recurrent kinds have no ragged formulation here — fall back.
        if self.mcfg.kind == "mlp" and not self.mcfg.extra_local_model:
            widths = {int(np.prod(bl[0]["x"].shape[1:])) for bl in pulled}
            dtypes = {str(np.asarray(bl[0]["x"]).dtype) for bl in pulled}
            if len(widths) == 1 and len(dtypes) == 1:
                return "ragged"
        return "seq"

    # ------------------------------------------------------------------
    # sequential fallback (bit-identical to FLClient.train_local)
    # ------------------------------------------------------------------

    def _run_sequential(self, global_params, client, batches):
        step = make_small_step(self.mcfg, self.opt, self.prox_mu)
        params = global_params
        opt_state = self.opt.init(params)
        metrics: Dict[str, Any] = {}
        for b in batches:
            params, opt_state, metrics = step(params, opt_state, b, global_params)
        delta = tree_sub(params, global_params)
        n_seen = len(batches) * client.data.batch_size
        return delta, float(n_seen), {k: float(v) for k, v in metrics.items()}

    # ------------------------------------------------------------------
    # compile cache
    # ------------------------------------------------------------------

    def _get_fn(self, key: tuple, builder: Callable) -> Callable:
        fn = self._compiled.get(key)
        if fn is None:
            self.stats.compiles += 1
            self._c_compiles.inc()
            fn = self._compiled[key] = builder()
            self.last_wave["cache_hit"] = False
        else:
            self.stats.cache_hits += 1
            self.last_wave["cache_hit"] = True
        return fn

    # ------------------------------------------------------------------
    # dense path: vmap over the client axis (+ shard_map under a mesh)
    # ------------------------------------------------------------------

    def _wave_partition(self) -> Tuple[Any, int]:
        """(PartitionSpec entry, shard count) for the wave's client axis
        under the active mesh + logical rules."""
        rules = dict(DEFAULT_CLIENT_RULES)
        if self.rules:
            rules.update(self.rules)
        rule = rules.get("clients")
        if isinstance(rule, str):
            rule = (rule,)
        names = set(getattr(self.mesh, "axis_names", ()))
        axes = tuple(a for a in (rule or ()) if a in names)
        n = 1
        for a in axes:
            n *= self.mesh.shape[a]
        if not axes or n == 1:
            return None, 1
        return (axes[0] if len(axes) == 1 else axes), n

    def _build_dense(self, entry) -> Callable:
        step = build_step_fn(self.mcfg, self.opt, self.prox_mu)
        opt = self.opt

        def one(gp, bx, by, key):
            opt_state = opt.init(gp)

            def body(carry, sb):
                params, ost, k = carry
                k = jax.random.fold_in(k, 1)  # per-step stream position
                params, ost, m = step(params, ost,
                                      {"x": sb[0], "y": sb[1]}, gp)
                return (params, ost, k), m

            (params, _, _), ms = lax.scan(body, (gp, opt_state, key), (bx, by))
            delta = tree_sub(params, gp)
            return delta, jax.tree.map(lambda a: a[-1], ms)

        wave = jax.vmap(one, in_axes=(None, 0, 0, 0))
        if entry is not None:
            cp = P(entry)
            wave = jax.shard_map(
                wave, mesh=self.mesh,
                in_specs=(P(), cp, cp, cp), out_specs=cp,
                check_vma=False,
            )
        return jax.jit(wave)

    def _prepare_dense(self, clients, pulled, round_idx):
        """The dense wave's program and host arrays ``(xs, ys, keys)``."""
        xs = np.stack([np.stack([np.asarray(b["x"]) for b in bl])
                       for bl in pulled])                       # (C,S,B,...)
        ys = np.stack([np.stack([np.asarray(b["y"]) for b in bl])
                       for bl in pulled])                       # (C,S,B)
        keys = _client_seed_keys(round_idx, [c.client_id for c in clients])
        C = len(clients)
        entry, nshard = self._wave_partition() if self.mesh is not None else (None, 1)
        pad = (-C) % nshard
        if pad:  # mesh divisibility: repeat the last client as filler
            xs = np.concatenate([xs, np.repeat(xs[-1:], pad, 0)])
            ys = np.concatenate([ys, np.repeat(ys[-1:], pad, 0)])
            keys = np.concatenate([keys, np.repeat(keys[-1:], pad, 0)])
        key = ("dense", C + pad, xs.shape[1:], str(xs.dtype),
               ys.shape[2:], str(ys.dtype), entry)
        return self._get_fn(key, lambda: self._build_dense(entry)), (xs, ys, keys)

    # ------------------------------------------------------------------
    # ragged path: clients are grouped_matmul groups
    # ------------------------------------------------------------------

    def _build_ragged(self, C: int) -> Callable:
        opt, mu, impl = self.opt, self.prox_mu, self.gmm_impl

        def loss_fn(sp, anchor, x, y, gs, seg):
            # forward: every dense layer is one grouped matmul over the
            # wave's row block (rows pre-sorted by client = group)
            denom = jnp.maximum(gs, 1).astype(jnp.float32)
            h = x
            for lyr in sp["main"]["layers"]:
                h = jax.nn.relu(
                    grouped_matmul(h, lyr["w"], gs, impl=impl)
                    + jnp.take(lyr["b"], seg, axis=0)
                )
            head = sp["main"]["head"]
            logits = (grouped_matmul(h, head["w"], gs, impl=impl)
                      + jnp.take(head["b"], seg, axis=0))
            row_ce = (jax.nn.logsumexp(logits, -1)
                      - jnp.take_along_axis(logits, y[:, None], -1)[:, 0])
            ce_c = jax.ops.segment_sum(row_ce, seg, num_segments=C) / denom
            hit = (jnp.argmax(logits, -1) == y).astype(jnp.float32)
            acc_c = jax.ops.segment_sum(hit, seg, num_segments=C) / denom
            loss_c = ce_c
            if mu > 0.0:
                sq_c = sum(
                    jnp.sum(
                        jnp.square(p.astype(jnp.float32)
                                   - a[None].astype(jnp.float32)),
                        axis=tuple(range(1, p.ndim)),
                    )
                    for p, a in zip(jax.tree.leaves(sp),
                                    jax.tree.leaves(anchor))
                )
                loss_c = loss_c + 0.5 * mu * sq_c
            # total = Σ_c loss_c: grads w.r.t. the stacked params are the
            # per-client grads (client c's slice only sees client c's rows)
            return jnp.sum(loss_c), {"ce": ce_c, "acc": acc_c, "loss": loss_c}

        def wave(anchor, xs, ys, gs, seg, keys):
            sp0 = jax.tree.map(
                lambda g: jnp.broadcast_to(g, (C,) + g.shape), anchor)
            ost0 = jax.vmap(opt.init)(sp0)

            def body(carry, sb):
                sp, ost, ks = carry
                ks = jax.vmap(lambda k: jax.random.fold_in(k, 1))(ks)
                (_, m), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                    sp, anchor, sb[0], sb[1], gs, seg)
                grads = jax.vmap(lambda g: clip_by_global_norm(g, 10.0)[0])(grads)
                sp, ost = jax.vmap(opt.update)(grads, ost, sp)
                return (sp, ost, ks), m

            (sp, _, _), ms = lax.scan(body, (sp0, ost0, keys), (xs, ys))
            delta = jax.tree.map(
                lambda p, g: p - g[None].astype(p.dtype), sp, anchor)
            return delta, jax.tree.map(lambda a: a[-1], ms)

        return jax.jit(wave)

    def _prepare_ragged(self, clients, pulled, round_idx):
        """The ragged wave's program and host arrays
        ``(xs, ys, gs, seg, keys)``."""
        C, S = len(clients), len(pulled[0])
        sizes = np.array([bl[0]["x"].shape[0] for bl in pulled], np.int64)
        width = int(np.prod(pulled[0][0]["x"].shape[1:]))  # same for all (checked)
        xs = np.stack([
            np.concatenate([np.asarray(pulled[c][s]["x"]).reshape(sizes[c], width)
                            for c in range(C)])
            for s in range(S)
        ])                                                      # (S, M, D)
        ys = np.stack([
            np.concatenate([np.asarray(pulled[c][s]["y"]) for c in range(C)])
            for s in range(S)
        ])                                                      # (S, M)
        # traced group metadata: the compiled program is reused across waves
        # with the same (C, S, M, D) envelope, whatever the row split
        gs = sizes.astype(np.int32)
        seg = np.repeat(np.arange(C, dtype=np.int32), sizes)
        keys = _client_seed_keys(round_idx, [c.client_id for c in clients])
        key = ("ragged", self.gmm_impl, C, xs.shape[1:], str(xs.dtype),
               str(ys.dtype))
        return (self._get_fn(key, lambda: self._build_ragged(C)),
                (xs, ys, gs, seg, keys))

    # ------------------------------------------------------------------

    def _split(self, deltas, metrics, clients, pulled):
        """Unstack the wave's outputs into per-client results.  The deltas
        stay on the device: one dispatch of :func:`_unstack` makes each
        client's delta its own ``jax.Array`` (mesh-pad filler rows are
        dropped), and only the metrics come to the host."""
        self.last_wave["platform"] = next(
            iter(jax.tree.leaves(deltas)[0].devices())).platform
        with span("wave.wait", self._trace, self._tenant, "train"):
            # what the metrics' fetch below waits for anyway
            jax.block_until_ready((deltas, metrics))
        d2h = sum(a.nbytes for a in jax.tree.leaves(metrics))
        self._c_d2h.inc(d2h)
        with span("wave.fetch", self._trace, self._tenant, "train", d2h_bytes=d2h):
            per_client = _unstack(deltas)[:len(clients)]
            metrics = jax.device_get(metrics)
            out = []
            for i, (delta, bl) in enumerate(zip(per_client, pulled)):
                m = {k: float(v[i]) for k, v in metrics.items()}
                n_seen = len(bl) * (bl[0]["x"].shape[0] if bl else 0)
                out.append((delta, float(n_seen), m))
        return out


@jax.jit
def _unstack(stacked: PyTree) -> List[PyTree]:
    """Every row of a wave's stacked per-client tree as a tree of its own,
    in one program (jit keys it on the stacked shapes, so there is one for
    each wave program); eager per-client, per-leaf slicing would cost a
    dispatch each."""
    rows = jax.tree.leaves(stacked)[0].shape[0]
    return [jax.tree.map(lambda a, _i=i: a[_i], stacked) for i in range(rows)]
