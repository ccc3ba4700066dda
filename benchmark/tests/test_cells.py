"""Every cell's world, traffic, window and check at a tiny size on the
CPU: the reference agrees with the served path, the control and each
fault of the timed path make `correct` false. What differs between
deployments (the numbers the control fails, the field a fault alters)
comes from the cell's deployment module."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from benchmark import loader, world
from benchmark.tests import tiny

CELLS = [c["name"] for c in tiny.cells()]


def _deployment(cell):
    config = next(c["config"] for c in tiny.cells() if c["name"] == cell)
    return loader.deployment(world.load_config(config))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_correct(cell):
    result = tiny.run(cell)
    assert result["correct"], result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "setup_s" in result["metrics"]
    assert list(result)[-1] == "compared"


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    result = tiny.run(cell, use_control=True)
    assert not result["correct"]
    for name in _deployment(cell).CONTROL_FAILS:
        c = result["compared"][name]
        assert c["value"] > c["limit"], name


def _state_unchanged(instance, _dep):
    engine = instance.pipeline_engine
    build = engine._build_step_blob

    def wrap():
        step = engine._step_blob

        def unchanged(params, state, *rest):
            kept = jax.tree_util.tree_map(jnp.copy, state)
            _, *others = step(params, state, *rest)
            return (kept, *others)

        engine._step_blob = unchanged

    def rebuild():
        # the engine rebuilds its step when its stages change
        build()
        wrap()

    engine._build_step_blob = rebuild
    wrap()


def _half_left_out(instance, _dep):
    packer = instance.pipeline_engine.packer
    pack = packer.pack_events

    def half(events, tokens):
        keep = max(1, len(events) // 2)
        return pack(events[:keep], tokens[:keep])

    packer.pack_events = half


def _value_altered(instance, dep):
    """The first event of each batch that has the deployment's
    `FAULT_FIELD` gets 1.0 added to it."""
    packer = instance.pipeline_engine.packer
    pack = packer.pack_events
    name = dep.FAULT_FIELD

    def altered(events, tokens):
        events = list(events)
        for i, event in enumerate(events):
            if hasattr(event, name):
                events[i] = dataclasses.replace(
                    event, **{name: getattr(event, name) + 1.0})
                break
        return pack(events, tokens)

    packer.pack_events = altered


FAULTS = [(cell, fault) for cell in CELLS
          for fault in (_state_unchanged, _half_left_out, _value_altered)]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_fault_is_not_correct(cell, fault):
    dep = _deployment(cell)
    result = tiny.run(cell, fault=lambda instance: fault(instance, dep))
    assert not result["correct"], result["compared"]
