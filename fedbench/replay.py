"""The plain federated reference, and the comparison that decides ``correct``.

A run checks two stretches of rounds, each from the benchmark's own
initial weights.  The set-up's first rounds, driven with the window's own
calls, check the trainer over consecutive rounds.  After the window, one
round at each end of the range of batch-size compositions the window can
draw (``generate.check_participants``) checks the compiled programs the
window holds at the sizes farthest from the set-up's; there the harness
picks the participants.  :class:`Recorder` keeps, for each round, the
finishers in the order the trainer folded them and a deep copy of each
finisher's data pipeline as COLLECT found it, and what the program
produced: the global parameters and test loss after every round, the
norm of every leaf of every client's delta in the first round of a
stretch, and, after the window, every client's delta itself.

After the window has closed and the program's state is freed,
:func:`follow` replays each stretch in plain ``jax.numpy``: each finisher
pulls the same batches from its copy and takes ``local_steps`` SGD steps
on the mean cross-entropy, with the gradient clipped to a global norm of
10, from the round's global parameters; the deltas are folded by
weighted FedAvg (weight = examples seen).  It imports nothing of the
program's models, executor or aggregation: it shares with the program
only the inputs (the data, the initial weights the benchmark made, the
finishers the trainer picked).

:func:`compare` reduces the two sides to six numbers, each a gap of the
program from the reference; ``limits/<cell>.json`` holds the limit of
each and the readings it was set from.
"""
from __future__ import annotations

import copy
import importlib.util
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

HERE = Path(__file__).resolve().parent

#: the global-norm clip of a local step (the program's documented rule)
CLIP_NORM = 10.0
#: a leaf whose reference update is under this share of the median leaf's
#: moves by round-off alone and is left out of the gaps of norms
NEGLIGIBLE_LEAF = 1e-3
#: the numbers compared, in the order they are printed
NUMBERS = ("loss_gap", "update_gap", "change_gap", "client_gap", "client_dir_gap",
           "client_sign_gap")


def load_module(path: Path, name: str):
    """A module of the benchmark found by its file (a reference, a metric)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reference(kind: str, root: Path = HERE):
    return load_module(root / "reference" / f"{kind}.py", f"fedbench_reference_{kind}")


def leaf_norms(tree) -> np.ndarray:
    """Euclidean norm of every leaf, in float64, on the host."""
    import jax

    return np.asarray([float(np.linalg.norm(np.asarray(leaf, np.float64).ravel()))
                       for leaf in jax.tree.leaves(tree)])


def change_norms(theta, theta0) -> np.ndarray:
    import jax

    return np.asarray([
        float(np.linalg.norm((np.asarray(a, np.float64) - np.asarray(b, np.float64)).ravel()))
        for a, b in zip(jax.tree.leaves(theta), jax.tree.leaves(theta0))])


def _kept(ref: np.ndarray) -> np.ndarray:
    """The leaves the reference moves: at least :data:`NEGLIGIBLE_LEAF` of
    the median leaf's norm."""
    return ref >= NEGLIGIBLE_LEAF * float(np.median(ref))


def worst_leaf_gap(prog: np.ndarray, ref: np.ndarray) -> float:
    """Largest ``|norm_prog - norm_ref|`` over the leaves, each measured
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger; leaves the reference leaves all but unmoved
    (under :data:`NEGLIGIBLE_LEAF` of the median) are left out."""
    med = float(np.median(ref))
    if not med > 0.0:
        return float("inf") if np.any(prog > 0) else 0.0
    keep = _kept(ref)
    gaps = np.abs(prog - ref)[keep] / np.maximum(ref[keep], med)
    return float(np.max(gaps))


def delta_turn(cos: np.ndarray, prog: np.ndarray, ref: np.ndarray) -> float:
    """``1 - cos`` between the program's and the reference's whole delta
    (every leaf together), from the per-leaf cosines and norms: 0 for the
    same direction, 2 for a delta of the opposite sign."""
    dot = float(np.sum(cos * prog * ref))
    return 1.0 - dot / max(float(np.linalg.norm(prog) * np.linalg.norm(ref)), 1e-300)



@dataclass
class CheckedRound:
    cids: List[int]
    data: List[Any]             # deep copies of the finishers' ClientDatasets


@dataclass
class Readings:
    """One stretch of rounds on one side of the comparison (the program,
    the reference, a control).  Round ``r`` of the stretch leaves
    ``thetas[r]``; ``theta0`` is where the stretch starts."""

    theta0: Any
    thetas: List[Any] = field(default_factory=list)
    test_losses: List[float] = field(default_factory=list)
    #: per round, every client's per-leaf delta norms (the rounds kept)
    client_norms: Dict[int, List[np.ndarray]] = field(default_factory=dict)
    #: per round, every client's delta (the program's, the rounds kept)
    client_deltas: Dict[int, List[Any]] = field(default_factory=dict)
    #: per round, every client's per-leaf cosine with the reference's delta
    client_cos: Dict[int, List[np.ndarray]] = field(default_factory=dict)

    def update(self, r: int):
        """The global model's change in round ``r``: (after, before)."""
        return self.thetas[r], (self.thetas[r - 1] if r else self.theta0)


class Recorder:
    """Watches a stretch of rounds through the trainer's public state:
    call the hooks as the round leaves each phase.  ``norms_rounds`` are
    the rounds whose clients' delta norms are kept, ``delta_rounds`` those
    whose clients' deltas are kept whole."""

    def __init__(self, theta0_host, n_rounds: int, norms_rounds: Sequence[int] = (0,),
                 delta_rounds: Sequence[int] = ()):
        self.n_rounds = n_rounds
        self.norms_rounds, self.delta_rounds = set(norms_rounds), set(delta_rounds)
        self.rounds: List[CheckedRound] = []
        self.program = Readings(theta0=theta0_host)

    def after_dispatch(self, st) -> None:
        cids = [cid for cid, _ in st.finishers]
        self.rounds.append(CheckedRound(
            cids, [copy.deepcopy(st.by_id[c].data) for c in cids]))

    def after_collect(self, st) -> None:
        import jax

        r = len(self.rounds) - 1
        deltas = [d for d, _ in st.deltas]
        if r in self.norms_rounds:
            self.program.client_norms[r] = [leaf_norms(d) for d in deltas]
        if r in self.delta_rounds:
            self.program.client_deltas[r] = jax.device_get(deltas)

    def after_report(self, tr, st) -> None:
        import jax

        self.program.test_losses.append(float(st.rec["test_loss"]))
        self.program.thetas.append(jax.device_get(tr.params["main"]))


def follow(ref, m: Dict[str, Any], theta0_host, rounds: List[CheckedRound],
           steps: int, lr: float, test: Dict[str, np.ndarray], *,
           dtype: str = "float32", precision: Optional[str] = "highest",
           fault: Optional[str] = None, norms_rounds: Sequence[int] = (0,),
           delta_rounds: Sequence[int] = (), against: Sequence[Readings] = ()) -> Readings:
    """A stretch of rounds in plain ``jax.numpy``.  ``dtype``/``precision``
    set the arithmetic (the reference: float32 at "highest"; the control:
    bfloat16 throughout).  ``fault`` plants one of the faults the check
    must catch: ``"half_batch"`` (each step's mean over the first half of
    its batch) or ``"unchanged"`` (every round leaves the model as it
    was).  For each side in ``against`` that kept a round's client deltas,
    the per-leaf cosine of each with this side's delta is written into
    that side's ``client_cos``; ``delta_rounds`` keeps this side's own."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    dt = jnp.dtype(dtype)
    prec = None if precision in (None, "default") else precision

    def ce(p, x, y):
        z = ref.logits(p, x, m, prec)
        return jnp.mean(jax.nn.logsumexp(z, -1)
                        - jnp.take_along_axis(z, y[:, None], -1)[:, 0])

    def step(p, xy):
        x, y = xy
        if fault == "half_batch":
            x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
        g = jax.grad(ce)(p, x, y)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(a)) for a in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, CLIP_NORM / jnp.maximum(norm, 1e-9)).astype(dt)
        return jax.tree.map(lambda a, b: a - (lr * scale) * b, p, g), None

    def norms(d):
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                          for a in jax.tree.leaves(d)])

    @jax.jit
    def local(p, xs, ys):
        q, _ = lax.scan(step, p, (xs, ys))
        d = jax.tree.map(jnp.subtract, q, p)
        return d, norms(d)

    @jax.jit
    def cosines(d, other):
        dots = jnp.stack([jnp.sum(a.astype(jnp.float32) * b.astype(jnp.float32))
                          for a, b in zip(jax.tree.leaves(d), jax.tree.leaves(other))])
        return dots / jnp.maximum(norms(d) * norms(other), 1e-30)

    @jax.jit
    def fold(acc, d, w):
        return jax.tree.map(lambda a, b: a + w.astype(dt) * b, acc, d)

    @jax.jit
    def apply(theta, acc):
        return jax.tree.map(jnp.add, theta, acc)

    # the test set is an argument, not a constant, so that one compiled
    # program serves every seed
    tx = jnp.asarray(test["x"], dt)
    ty = jnp.asarray(test["y"])
    test_loss = jax.jit(ce)

    theta = jax.tree.map(lambda a: jnp.asarray(a, dt), theta0_host)
    out = Readings(theta0=jax.device_get(theta))
    n_leaves = len(jax.tree.leaves(theta))
    for r, rnd in enumerate(rounds):
        sides = [s for s in against if r in s.client_deltas]
        for s in sides:
            s.client_cos[r] = []
        if r in norms_rounds:
            out.client_norms[r] = []
        if r in delta_rounds:
            out.client_deltas[r] = []
        if fault != "unchanged":
            data = copy.deepcopy(rnd.data)
            pulled = [[ds.next_batch() for _ in range(steps)] for ds in data]
            weights = np.asarray([steps * b[0]["x"].shape[0] for b in pulled], np.float64)
            acc = jax.tree.map(jnp.zeros_like, theta)
            for i, (batches, w) in enumerate(zip(pulled, weights / weights.sum())):
                xs = jnp.asarray(np.stack([b["x"] for b in batches]), dt)
                ys = jnp.asarray(np.stack([b["y"] for b in batches]))
                d, n = local(theta, xs, ys)
                if r in norms_rounds:
                    out.client_norms[r].append(n)
                if r in delta_rounds:
                    out.client_deltas[r].append(jax.device_get(d))
                for s in sides:
                    s.client_cos[r].append(cosines(d, s.client_deltas[r][i]))
                acc = fold(acc, d, jnp.float32(w))
            theta = apply(theta, acc)
        else:
            zeros = jax.device_get(jax.tree.map(jnp.zeros_like, theta))
            for key, store in ((norms_rounds, out.client_norms), (delta_rounds, out.client_deltas)):
                if r in key:
                    store[r] = [zeros if store is out.client_deltas else np.zeros(n_leaves)
                                for _ in rnd.cids]
            for s in sides:
                # a zero delta has no direction: it turns by a right angle
                s.client_cos[r] = [np.zeros(n_leaves) for _ in rnd.cids]
        out.test_losses.append(float(test_loss(theta, tx, ty)))
        out.thetas.append(jax.device_get(theta))
    out.client_norms = {r: [np.asarray(v, np.float64) for v in jax.device_get(vs)]
                        for r, vs in out.client_norms.items()}
    for s in against:
        s.client_cos = {r: [np.asarray(v, np.float64) for v in jax.device_get(vs)]
                        for r, vs in s.client_cos.items()}
    return out


def compare(prog: Sequence[Readings], ref: Sequence[Readings]) -> Dict[str, float]:
    """The six gaps of the program's stretches ``prog`` from the
    reference's ``ref`` (see ``PERF.md``): the first stretch is the
    set-up's consecutive rounds, the others the rounds after the window."""
    shapes_ok = len(prog) == len(ref) and all(
        len(p.test_losses) == len(r.test_losses) and set(p.client_norms) == set(r.client_norms)
        and all(len(p.client_norms[k]) == len(r.client_norms[k]) for k in r.client_norms)
        and set(p.client_cos) == set(p.client_deltas)
        for p, r in zip(prog, ref))
    if not shapes_ok:
        return {k: float("inf") for k in NUMBERS}
    lp = np.concatenate([p.test_losses for p in prog])
    lr = np.concatenate([r.test_losses for r in ref])
    first = (prog[0], ref[0])
    # the update of the first round of each stretch (each starts from the
    # benchmark's weights), and every round after the window
    updates = [(p, r, k) for p, r in zip(prog, ref)
               for k in (range(len(r.thetas)) if p is not prog[0] else (0,))]
    clients = [(pn, rn) for p, r in zip(prog, ref) for k in r.client_norms
               for pn, rn in zip(p.client_norms[k], r.client_norms[k])]
    turns = [(c, pn, rn) for p, r in zip(prog, ref) for k in p.client_cos
             for c, pn, rn in zip(p.client_cos[k], p.client_norms[k], r.client_norms[k])]
    return {
        # the global model's test loss after each checked round
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        # the update of the global model in a round, by the worst leaf
        "update_gap": max(worst_leaf_gap(change_norms(*p.update(k)), change_norms(*r.update(k)))
                          for p, r, k in updates),
        # the change after the set-up's last checked round, by the worst leaf
        "change_gap": worst_leaf_gap(change_norms(first[0].thetas[-1], first[0].theta0),
                                     change_norms(first[1].thetas[-1], first[1].theta0)),
        # every checked client's delta, by its worst leaf
        "client_gap": max(worst_leaf_gap(pn, rn) for pn, rn in clients),
        # every client's delta after the window against the reference's:
        # its turn away from it (1 - cos), and how far it points backwards
        # (-cos, where the cosine is negative), so that a sign is seen
        "client_dir_gap": max(delta_turn(*t) for t in turns) if turns else float("inf"),
        "client_sign_gap": (max(max(0.0, delta_turn(*t) - 1.0) for t in turns)
                            if turns else float("inf")),
    }


def judge(numbers: Dict[str, float], limits: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    """Each number beside its limit.  A number that a cell's limits mark
    ``not_compared`` (with the reason: no upper reading) is printed with
    the limit null."""
    return {k: {"value": numbers[k],
                "limit": None if "not_compared" in limits[k] else float(limits[k]["limit"])}
            for k in NUMBERS}


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    compared = [c for c in checks.values() if c["limit"] is not None]
    return bool(compared) and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"] for c in compared)
