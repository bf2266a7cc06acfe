"""What the tests read of a jaxpr: one walker, shared."""

import jax


def eqns(jaxpr, primitive: str):
    """Every equation of ``primitive`` in ``jaxpr``, inside its sub-jaxprs
    (scans, conditionals, custom derivatives) too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from eqns(sub, primitive)
