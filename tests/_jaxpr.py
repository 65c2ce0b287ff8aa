"""Walking a traced program: what the tests ask of a jaxpr's equations."""
import jax


def walk(jaxpr, enclosing=()):
    """Every equation of ``jaxpr`` and of the jaxprs in its equations'
    parameters, each with the names of the primitives that enclose it."""
    for eqn in jaxpr.eqns:
        yield eqn, enclosing
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from walk(sub, enclosing + (eqn.primitive.name,))


def pallas_calls(jaxpr):
    """``(kernel name, enclosing primitives)`` of every ``pallas_call``."""
    return [(eqn.params["name"], enclosing) for eqn, enclosing in walk(jaxpr)
            if eqn.primitive.name == "pallas_call"]


def pallas_names(jaxpr):
    return [name for name, _ in pallas_calls(jaxpr)]
