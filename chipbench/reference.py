"""Follow the first steps of a training cell with the plain reference.

The reference starts from the same seeded weights and the same batches as
the program, and takes the configuration's own SGD steps. It runs once the
timed window has closed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import refmath


def follow(family, cfg: dict, mix: dict, params0: dict, items: list, *,
           precision: str = "highest", fault: str | None = None) -> dict:
    """Losses of ``len(items)`` reference steps, the first gradient as the
    optimizer gets it (the momentum state after one step, with momentum),
    and the parameters after the last step, the arrays fetched to the host
    so that the device holds one step's state at a time.

    ``fault`` plants one of the program faults the comparison must catch
    (see the family's ``plant``) into every step, so that the reference
    stands in for a broken program.
    """
    t = cfg["train"]
    lr, mu = t["lr"], t["momentum"]
    if fault is not None:
        planted = [family.plant(cfg, mix, item, fault) for item in items]
        mix, items = planted[0][0], [item for _, item in planted]
    loss = family.make_loss(cfg, mix, precision)

    def mean_loss(p, *inputs):
        rows = loss(p, *inputs)
        return jnp.mean(rows), rows

    @jax.jit
    def step(p, v, inputs):
        with jax.default_matmul_precision("highest"):
            (_, rows), g = jax.value_and_grad(mean_loss, has_aux=True)(p, *inputs)
        p, v = refmath.sgd(p, g, lr, mu, v)
        return rows, p, v, g

    p = params0
    v = {k: jnp.zeros_like(w) for k, w in p.items()} if mu else None
    losses, first = [], None
    for item in items:
        rows, p, v, g = step(p, v, family.ref_inputs(cfg, item))
        if first is None:
            first = {k: np.asarray(a) for k, a in (v if mu else g).items()}
        losses.append(float(np.mean(np.asarray(rows, np.float64))))
        del g
    return {"losses": losses, "first_grad": first,
            "params": {k: np.asarray(a) for k, a in p.items()}}
