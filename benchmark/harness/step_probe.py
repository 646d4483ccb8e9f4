"""Stands between a trainer and its compiled step for the first steps.

``Trainer.fit`` and ``LMTrainer.fit_tables`` build their state and their
compiled step inside one call and hand out neither. The ``correct`` comparison
needs the timed path's own first steps: the loss of each, the first gradient as
the optimizer got it, and the parameters' change after the second. So the
harness wraps the step factory the trainer calls (named by the family module) and gets
this object in the step's place. It

- replaces the parameters with the benchmark's seeded ones before step 1
  (``harness/weights.py``; the optimizer state is all zeros either way),
- keeps the inputs and the loss of steps 1 and 2 (``FOLLOW``),
- reads the first gradient's per-leaf norm from Adam's first moment after step
  1 (``mu = (1 - b1) * g``), and the per-leaf norm of ``params - seeded`` after
  step 2 (the seeded values are made again inside that reduction, not kept),
- and from step 3 on only counts calls.

It is the same compiled step, the same state and the same loader that go on
into the timed window; nothing is built twice.
"""

from __future__ import annotations

import contextlib
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness.weights import seed_key, seeded_weights

FOLLOW = 2          # steps the reference follows (two, not three: the float32
                    # reference of a whole timed batch has to stay shorter
                    # than the window)
ADAM_B1 = 0.9       # optax.adam's default; the trainers pass none


def path_names(path) -> tuple:
    return tuple(str(getattr(k, "key", getattr(k, "name", k))) for k in path)


def split_ref_key(ref_key: str):
    name, _, layer = ref_key.partition("@")
    return name, (int(layer) if layer else None)


def program_tree(template, mapping: dict, ref_weights: dict):
    """The reference-named weights laid out as the program's parameter tree.
    ``mapping``: program path (tuple of names) -> reference key
    (``name`` or ``blk.name@layer``). Every program leaf has to be mapped and
    to hold as many numbers as its reference leaf."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
    out = []
    for path, leaf in leaves:
        names = path_names(path)
        if names not in mapping:
            raise KeyError(f"program leaf {'/'.join(names)} has no reference "
                           f"leaf: the family's leaf map is out of date")
        name, layer = split_ref_key(mapping[names])
        value = ref_weights[name] if layer is None else ref_weights[name][layer]
        if value.size != leaf.size:
            raise ValueError(f"{'/'.join(names)} holds {leaf.shape}, reference "
                             f"{mapping[names]} holds {value.shape}")
        out.append(value.reshape(leaf.shape).astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def _norm(x):
    x = x.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(x * x))


class StepProbe:
    def __init__(self, inner, seed: int, spec: dict, mapping: dict):
        self._inner = inner
        self._seed, self._spec, self._mapping = seed, spec, mapping
        self.calls = 0
        self.stamps = [time.time()]     # built, then each of the first calls
        self.batches: list = []         # host copies of steps 1..3's inputs
        self.losses: list = []          # device scalars until collect()
        self.grad_norms = None
        self.delta_norms = None

    def __getattr__(self, name):        # batch_sharding, place_state, lower...
        return getattr(self._inner, name)

    def _seeded_tree(self, template, key):
        """``key`` is an argument of every jitted function that calls this,
        never a constant in it: a program with the seed baked in would miss
        the compile cache on every new seed."""
        return program_tree(template, self._mapping,
                            seeded_weights(key, self._spec))

    def _swap(self, state, batch):
        """Seeded parameters where the program's were. A leaf the program has
        placed keeps its sharding; one it left unplaced (eager init on the
        first device) is made replicated over the devices the batch is on,
        which is where the step would have moved it."""
        from jax.sharding import NamedSharding, PartitionSpec

        old = state.params
        feed = batch.sharding
        over = (NamedSharding(feed.mesh, PartitionSpec())
                if isinstance(feed, NamedSharding) else feed)
        shardings = jax.tree.map(
            lambda x: x.sharding if getattr(x, "committed", False) else over,
            old)
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), old)
        # the program's values go first, so that the two sets never sit on
        # the device together and memory_peak_bytes stays the program's
        for leaf in jax.tree.leaves(old):
            leaf.delete()
        new = jax.jit(lambda key: self._seeded_tree(shapes, key),
                      out_shardings=shardings)(seed_key(self._seed))
        return state.replace(params=new)

    def _mu_norms(self, opt_state):
        mus = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(opt_state)[0]:
            names = path_names(path)
            if "mu" in names:
                mus[names[names.index("mu") + 1:]] = leaf
        if set(mus) != set(self._mapping):
            raise KeyError("the optimizer state holds no first moment per "
                           "parameter leaf; the gradient cannot be read from it")
        return jax.jit(lambda m: {k: _norm(x) / (1 - ADAM_B1)
                                  for k, x in m.items()})(mus)

    def _delta_norms(self, params):
        def reduce(p, key):
            seeded = self._seeded_tree(p, key)
            flat = jax.tree_util.tree_flatten_with_path(
                jax.tree.map(jnp.subtract, p, seeded))[0]
            return {path_names(path): _norm(x) for path, x in flat}
        return jax.jit(reduce)(params, seed_key(self._seed))

    def __call__(self, state, *args):
        n = self.calls
        self.calls += 1
        if n <= FOLLOW:
            self.stamps.append(time.time())
        if n == 0:
            state = self._swap(state, args[0])
        if n < FOLLOW:
            self.batches.append(tuple(np.asarray(a) for a in args[:2]))
        state, metrics = self._inner(state, *args)
        if n < FOLLOW:
            self.losses.append(metrics["loss"])
        if n == 0:
            self.grad_norms = self._mu_norms(state.opt_state)
        if n == FOLLOW - 1:
            self.delta_norms = self._delta_norms(state.params)
        return state, metrics

    def collect(self) -> dict:
        """Host numbers, keyed by reference leaf names."""
        if self.calls < FOLLOW:
            raise RuntimeError(f"the step ran {self.calls} times, the "
                               f"comparison follows {FOLLOW}")
        rekey = lambda d: {self._mapping[k]: float(v) for k, v in d.items()}
        return {"losses": [float(x) for x in self.losses],
                "grad_norms": rekey(self.grad_norms),
                "delta_norms": rekey(self.delta_norms)}


@contextlib.contextmanager
def probing(factory: tuple, seed: int, spec: dict, mapping: dict):
    """Wrap ``module.attr`` (the step factory a trainer calls) for the length
    of the block; yields a list that receives the one probe made."""
    module = importlib.import_module(factory[0])
    original = getattr(module, factory[1])
    made: list = []

    def wrapped(*a, **kw):
        probe = StepProbe(original(*a, **kw), seed, spec, mapping)
        made.append(probe)
        return probe

    setattr(module, factory[1], wrapped)
    try:
        yield made
    finally:
        setattr(module, factory[1], original)
