"""Compile the selection kernels for a TPU v5e without one attached.

Interpret mode never meets Mosaic's tiling and VMEM checks, so these tests
lower the ``ops`` wrappers against a described ``v5e:2x2`` topology and
compile them with the TPU compiler that ships with libtpu.  ``ops`` asks
``jax.default_backend()`` to pick compiled Pallas; each test steers that
choice to the TPU branch with ``monkeypatch``.  Machines are vmapped as
``core/distributed`` does, so every input block is double-buffered exactly
as in a tree round.

The topology is described in a module-scoped fixture, never at import:
only one process at a time may load libtpu, and every xdist worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops

MACHINES = 256          # per-device machines of a tree wave: no operand fits
M_EVAL = 512            # |E|, the paper's eval-set size


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any libtpu failure means no TPU
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a cached entry compiled for a described chip cannot be read back
    # without one, so keep the persistent cache out of these compiles
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_dispatch(monkeypatch):
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct((MACHINES,) + shape, dtype,
                                 sharding=sharding)
            for shape, dtype in specs]


def _compile(fn, sharding, *specs) -> str:
    """Lower ``fn`` vmapped over machines for the described chip; returns
    the compiled program's text."""
    return (jax.jit(jax.vmap(fn)).lower(*_shapes(sharding, *specs))
            .compile().as_text())


F32, BF16, I8, I32 = jnp.float32, jnp.bfloat16, jnp.int8, jnp.int32
_CAPS = (4, 4, 4)


def _greedy(variant: str, k: int = 16, impl: str = "auto"):
    if variant == "knapsack+partition":
        return lambda X, E, cm, mask, w, g: ops.greedy_select(
            X, E, cm, mask, k, impl=impl, weights=w, budget=3.0,
            group_ids=g, caps=_CAPS)
    if variant == "int8":
        return lambda X, E, cm, mask, xs, xz: ops.greedy_select(
            X, E, cm, mask, k, impl=impl, x_scale=xs, x_zp=xz)
    return lambda X, E, cm, mask: ops.greedy_select(X, E, cm, mask, k,
                                                     impl=impl)


def _greedy_specs(variant: str, n: int, d: int, m: int = M_EVAL):
    xdt = {"int8": I8, "bf16": BF16}.get(variant, F32)
    specs = [((n, d), xdt), ((m, d), F32), ((m,), F32), ((n,), jnp.bool_)]
    if variant == "knapsack+partition":
        specs += [((n,), F32), ((n,), I32)]
    if variant == "int8":
        specs += [((n,), F32), ((n,), F32)]
    return specs


def _threshold(variant: str, k: int = 16):
    tau = 0.5
    if variant == "knapsack+partition":
        return lambda X, E, cm, mask, w, g: ops.threshold_select(
            X, E, cm, mask, tau, k, weights=w, budget=3.0, group_ids=g,
            caps=_CAPS)[0]
    if variant == "int8":
        return lambda X, E, cm, mask, xs, xz: ops.threshold_select(
            X, E, cm, mask, tau, k, x_scale=xs, x_zp=xz)[0]
    return lambda X, E, cm, mask: ops.threshold_select(
        X, E, cm, mask, tau, k)[0]


@pytest.mark.parametrize("variant", ["plain", "knapsack+partition", "int8",
                                     "bf16"])
@pytest.mark.parametrize("d", [64, 1024])
def test_greedy_select_compiles(one_chip, tpu_dispatch, variant, d):
    # per-machine blocks the estimate admits for every variant at this d
    n = 1000 if d == 64 else 256
    text = _compile(_greedy(variant), one_chip, *_greedy_specs(variant, n, d))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("variant", ["plain", "knapsack+partition", "int8"])
@pytest.mark.parametrize("d", [64, 1024])
def test_threshold_select_compiles(one_chip, tpu_dispatch, variant, d):
    specs = _greedy_specs(variant, 1000, d)
    text = _compile(_threshold(variant), one_chip, *specs)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("variant", ["plain", "int8", "weighted"])
@pytest.mark.parametrize("d", [64, 1024, 3072])
def test_exemplar_gains_compiles(one_chip, tpu_dispatch, variant, d):
    n = 1000
    specs = [((n, d), I8 if variant == "int8" else F32), ((M_EVAL, d), F32),
             ((M_EVAL,), F32)]
    if variant == "int8":
        specs += [((n,), F32), ((n,), F32)]
        fn = lambda X, E, cm, xs, xz: ops.exemplar_gains(  # noqa: E731
            X, E, cm, x_scale=xs, x_zp=xz)
    elif variant == "weighted":
        specs += [((M_EVAL,), F32)]
        fn = lambda X, E, cm, w: ops.exemplar_gains(  # noqa: E731
            X, E, cm, eval_weights=w)
    else:
        fn = ops.exemplar_gains
    assert "tpu_custom_call" in _compile(fn, one_chip, *specs)


def _itemsize(variant: str) -> int:
    return 1 if variant == "int8" else 4


def _admits(variant: str, n: int, d: int) -> bool:
    return ops._fits_vmem(n, M_EVAL, d, 256, x_itemsize=_itemsize(variant),
                          cols=3 if variant == "int8" else 1)


def _largest_admitted(variant: str, d: int, step: int = 256) -> int:
    n = step
    while _admits(variant, n + step, d):
        n += step
    return n


@pytest.mark.parametrize("variant", ["plain", "int8"])
@pytest.mark.parametrize("d", [64, 1024])
def test_vmem_estimate_agrees_with_mosaic(one_chip, tpu_dispatch, variant,
                                          d):
    """The largest block ``auto`` sends to Pallas compiles; one block past
    it ``auto`` keeps on the reference; Mosaic refuses a block the size of
    the old 12 MiB estimate's limit, and so does the estimate."""
    fn = _greedy(variant)
    n = _largest_admitted(variant, d)
    text = _compile(fn, one_chip, *_greedy_specs(variant, n, d))
    assert "tpu_custom_call" in text
    past = _compile(fn, one_chip, *_greedy_specs(variant, n + 256, d))
    assert "tpu_custom_call" not in past
    # the largest block the old estimate (12 MiB, X counted once, each
    # column n words, nothing double-buffered) sent to Pallas
    old = (12 * 2**20 - (M_EVAL * d + M_EVAL + 256 * M_EVAL) * 4) // (
        d * _itemsize(variant) + 20) // 256 * 256
    assert not _admits(variant, old, d)
    forced = _greedy(variant, impl="pallas")
    with pytest.raises(Exception, match="(?i)vmem"):
        _compile(forced, one_chip, *_greedy_specs(variant, old, d))

