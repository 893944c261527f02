"""The reference of a cell on more than one chip: where its state lies,
and how long each piece of it is held.

The reference (``reference/gpt2.py``) starts from the weights that
``train.weights_fn`` makes when it is given no shardings; on one chip they
lie on that chip whole.  A multi-chip cell's weights, momentum and
gradient together are more than one chip holds, so there each leaf is
split along its largest dimension that the chip count divides, over all
the cell's chips, and jit partitions the reference's functions from that
placement.  The placement is the benchmark's own: nothing of the program's
sharding rules is used.

Such a cell's first steps (``reference_steps``) compute what
``train.reference_steps`` computes, with the same reference functions in
the same order, but hold less at once: the first weights are made again
from the seed for the change at the end instead of being kept, and each
step's gradient is let go before the next step.  On a TPU the device's
buffers may never again reach above their highest mark less a program's
temporaries: a program whose temporaries the runtime has to place again
fails to load there.  At 15 layers of ``gpt2-60l`` on a 2x2 v5e host,
``train.reference_steps`` peaks at seven copies of the weights' share of
a chip (13.0 of 16.9 GB), after which the 5.2 GB its gradient program
needs no longer fit (RESOURCE_EXHAUSTED); this order holds at most five
at once.
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P
from reference import gpt2 as ref

AXIS = "chips"


def spec(shape, n: int) -> P:
    """``shape``'s largest dimension that ``n`` divides (the last of
    equals) over the chips; every other dimension whole."""
    for i in sorted(reversed(range(len(shape))), key=lambda i: -shape[i]):
        if shape[i] % n == 0:
            return P(*(AXIS if j == i else None for j in range(len(shape))))
    return P()


def placement(tree, devices):
    """A ``NamedSharding`` per leaf of ``tree`` (arrays or shapes)."""
    mesh = Mesh(np.array(devices), (AXIS,))
    return jax.tree.map(lambda x: NamedSharding(mesh, spec(x.shape,
                                                           len(devices))),
                        tree)


def reference_steps(train, cell, seed, steps, corpus, dtype=jnp.float32,
                    rows_used=None):
    """``train.reference_steps`` of a multi-chip cell: the same numbers,
    (losses, first-gradient norms, change norms), from a state held as
    the module docstring says."""
    m, mix = cell.model, cell.traffic
    eps = cell.config["layer_norm_epsilon"]
    data = corpus.reader(cell, seed)
    make = train.weights_fn(cell, seed, mix["source_layers"])
    params = jax.tree.map(lambda x: x.astype(dtype), make())
    mom = jax.tree.map(jnp.zeros_like, params)
    losses, gnorms = [], None
    opt = mix["optimizer"]
    muon = jax.jit(lambda p, mom, g, lr: ref.muon_nsgd(p, mom, g, lr, opt))
    with train._precision(dtype):
        for i in range(train.CHECK_STEPS):
            b = data.batch(i)
            toks, labels = b["tokens"], b["labels"]
            if rows_used is not None:
                toks, labels = toks[:rows_used], labels[:rows_used]
            lr = ref.wsd_lr(i, steps, mix["schedule"], mix["optimizer"]["lr"])
            lv, g = ref.loss_and_grads(params, m, jnp.asarray(toks),
                                       jnp.asarray(labels), eps,
                                       train.REF_ROWS)
            losses.append(float(lv))
            if i == 0:
                gnorms = train._floats(train._leaf_norms(g))
            params, mom = muon(params, mom, g, jnp.asarray(lr, dtype))
            del g
        change = train._floats(train._change_norms(params, make()))
    return losses, gnorms, change


def install(train):
    """Give ``train`` this placement and these steps for cells on more than
    one chip; a one-chip cell's reference stays as it was."""
    made, whole = train.weights_fn, train.reference_steps

    def weights_fn(cell, seed, layers, shardings=None):
        if shardings is None and cell.chips > 1:
            m = cell.model
            shapes = jax.eval_shape(lambda k: ref.init(k, m, layers),
                                    jax.random.PRNGKey(0))
            shardings = placement(shapes, jax.devices()[:cell.chips])
        return made(cell, seed, layers, shardings)

    def steps(cell, seed, steps, corpus, dtype=jnp.float32,
              rows_used=None):
        if cell.chips > 1:
            return reference_steps(train, cell, seed, steps, corpus, dtype,
                                   rows_used)
        return whole(cell, seed, steps, corpus, dtype, rows_used)

    train.weights_fn = weights_fn
    train.reference_steps = steps
