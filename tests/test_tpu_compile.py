"""Both Pallas kernels compile for a described TPU v5e at real widths.

Interpret mode runs the kernel body in Python and never meets the TPU's
tiling rules or its VMEM limit; the TPU compiler does. libtpu compiles for a
chip that is described and not attached, so these tests guard the chip path
without one. The topology is described inside a fixture, never at import:
only one process may load libtpu at a time, and every xdist worker imports
this file.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gemv_w4a8.ops import gemv_w4a8
from repro.kernels.swiftkv_decode.ops import swiftkv_decode


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot describe the chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the persistent
    # cache without one; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


# B, G (query heads per KV head) and S as the chip smoke serves them; Hkv = 8
# is h2o-danube-1.8b's and qwen3-8b's, D = 80 and 128 their head dims
B, HKV, G, S = 4, 8, 4, 2048


@pytest.mark.parametrize("kind", ["linear", "ring", "int8"])
@pytest.mark.parametrize("d", [80, 128])
def test_swiftkv_decode_compiles_for_v5e(one_chip, d, kind):
    kv_dtype = jnp.int8 if kind == "int8" else jnp.bfloat16
    args = [_spec(one_chip, (B, HKV * G, d), jnp.bfloat16),
            _spec(one_chip, (B, S, HKV, d), kv_dtype),
            _spec(one_chip, (B, S, HKV, d), kv_dtype),
            _spec(one_chip, (B,), jnp.int32)]
    kw = {}
    if kind == "ring":
        kw = {"ring": True, "window": 1024}
    if kind == "int8":
        kw = {"k_scale": _spec(one_chip, (B, HKV, S), jnp.bfloat16),
              "v_scale": _spec(one_chip, (B, HKV, S), jnp.bfloat16)}
    _assert_kernel(swiftkv_decode.lower(*args, **kw).compile())


def test_swiftkv_decode_mha_compiles_for_v5e(one_chip):
    """32 KV heads of 128 (llama2-7b's MHA): a 512-row tile of every head
    would not fit VMEM double-buffered, so ops caps block_k at 256."""
    args = [_spec(one_chip, (B, 32, 128), jnp.bfloat16),
            _spec(one_chip, (B, S, 32, 128), jnp.bfloat16),
            _spec(one_chip, (B, S, 32, 128), jnp.bfloat16),
            _spec(one_chip, (B,), jnp.int32)]
    _assert_kernel(swiftkv_decode.lower(*args).compile())


@pytest.mark.parametrize("k,n", [
    (2560, 6912),      # h2o-danube-1.8b's up/gate projection
    (768, 2560),       # K = 1.5 blocks: the wrapper pads K to block_k
])
def test_gemv_w4a8_compiles_for_v5e(one_chip, k, n):
    args = [_spec(one_chip, (8, k), jnp.bfloat16),
            _spec(one_chip, (k, n // 2), jnp.uint8),
            _spec(one_chip, (k // 128, n), jnp.float32)]
    _assert_kernel(gemv_w4a8.lower(*args).compile())


# ---------------------------------------------------------------------------
# the decode program moves no whole cache per tick
# ---------------------------------------------------------------------------

# instructions that name a buffer rather than produce one
_NO_BUFFER = {"parameter", "get-tuple-element", "tuple", "bitcast", "while"}


def _computations(hlo: str) -> tuple[dict, str]:
    """{computation name: its instruction lines}, and the entry's name."""
    comps, cur, entry = {}, None, None
    for line in hlo.splitlines():
        m = re.match(r"^(ENTRY )?%?([\w.\-]+) .*\{$", line)
        if m:
            cur, comps[m.group(2)] = m.group(2), []
            entry = cur if m.group(1) else entry
        elif line.startswith("}"):
            cur = None
        elif cur and line.strip():
            comps[cur].append(line.strip())
    return comps, entry


def _loop_bodies(comps: dict, comp: str) -> list[str]:
    """Every while body under ``comp``, outermost first."""
    out = []
    for ins in comps[comp]:
        if " while(" in ins:
            body = re.search(r"body=%?([\w.\-]+)", ins).group(1)
            out += [body] + _loop_bodies(comps, body)
    return out


def _instructions(lines: list[str]):
    """(name, dims, opcode, operand names, op_name) of each instruction."""
    for ins in lines:
        m = re.match(r"(?:ROOT )?%?([\w.\-]+) = (\S+?)(?:\{[^ ]*\})? "
                     r"([\w\-]+)\(([^)]*)\)", ins)
        if not m:
            continue
        dims = re.match(r"\w+\[([\d,]*)\]$", m.group(2))
        op = re.search(r'op_name="([^"]*)"', ins)
        yield (m.group(1), tuple(int(d) for d in dims.group(1).split(","))
               if dims and dims.group(1) else None, m.group(3),
               [o.strip().lstrip("%") for o in m.group(4).split(",")],
               op.group(1) if op else "")


def test_decode_block_updates_the_cache_in_place_for_v5e(one_chip):
    """The engine's decode block (``decode_multi``, K=2, cache donated) at
    qwen3-8b's widths: inside the tick loop and the layer loop nothing
    produces a buffer of the whole cache's shape but the ``kv_write``
    update of the buffer the loop carries, and nothing outside the
    ``attention`` scope produces one layer's ``[B, S, Hkv, Dh]`` slab. A
    layer loop that scanned the cache as per-layer inputs and outputs
    would slice each layer's slab out, restack the updated slabs into a
    fresh stack, and copy that into the tick loop's carry."""
    from repro.configs import get_config
    from repro.models.api import build_model

    n_layers, slots, max_len = 2, 4, 1024
    cfg = get_config("qwen3-8b").replace(n_layers=n_layers, vocab_size=18992,
                                         norm_eps=1e-6)
    model = build_model(cfg)
    spec = lambda tree: jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype), tree)
    params = spec(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    cache = spec(jax.eval_shape(lambda: model.init_cache(slots, max_len)))
    rows = lambda dtype: _spec(one_chip, (slots,), dtype)

    def block(params, tok, cache, active, budget, serials, emitted):
        toks, _, _, cache = model.decode_multi(
            params, tok, cache, active, budget, serials, emitted, 2)
        return toks, cache

    hlo = jax.jit(block, donate_argnums=(2,)).lower(
        params, rows(jnp.int32), cache, rows(jnp.bool_), rows(jnp.int32),
        rows(jnp.int32), rows(jnp.int32)).compile().as_text()
    comps, entry = _computations(hlo)
    bodies = _loop_bodies(comps, entry)
    assert len(bodies) >= 2, "expected a tick loop around a layer loop"
    whole = cache["k"].shape                       # [L, B, S, Hkv, Dh]
    slab = whole[1:]
    for body in bodies:
        made = {n: (o, ops) for n, _, o, ops, _ in _instructions(comps[body])}
        carry = {m.group(1) for m in (
            re.match(r"%?([\w.\-]+) = .* parameter\(0\)", ins)
            for ins in comps[body]) if m}
        for name, dims, opcode, operands, op_name in _instructions(
                comps[body]):
            if opcode in _NO_BUFFER:
                continue
            if dims == whole:
                # the update's target is the buffer the loop carries
                target = made.get(operands[0], ("", [""]))
                in_place = ("/kv_write/" in op_name
                            and target[0] == "get-tuple-element"
                            and target[1][0] in carry)
                assert in_place, (body, name, opcode, op_name)
            if dims == slab:
                assert "/attention/" in op_name, (body, name, opcode, op_name)
