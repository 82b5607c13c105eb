"""Compile the Pallas kernels, and the served decode quantum, for a
described TPU v5e at real widths.

Nothing runs: the TPU compiler, installed with JAX, compiles for a chip
that is described and not attached.  It refuses what interpret mode
cannot see — a block whose last two dims are not tile-aligned, a kernel
op with no TPU lowering, more VMEM than a kernel may use — so these
compiles guard the chip path at no chip time.  The topology is described
inside a fixture (never at import), and the persistent compilation cache
is off around the compiles (such entries cannot be read back without a
chip).
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import cost_model as cm
from repro.kernels import ops
from repro.models import build_model
from repro.models.model import path_keys
from repro.models.params import abstract_params
from repro.serving.engine import DEFAULT_LEVEL_TILES

SC = get_config("starcoder2-3b")
MB = get_config("mamba2-780m")
T = 2048                      # serving max_len
SLOTS = 8                     # serving batch slots
PAGE = 16
ATTN_TILES = sorted({(t["attention"]["bq"], t["attention"]["bkv"])
                     for t in DEFAULT_LEVEL_TILES}, reverse=True)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    cache_on = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # compiler logs stay off disk
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                 # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernels(fn, *args) -> str:
    """Compile ``fn`` for the described chip; -> its compiled HLO text,
    which must hold a Pallas kernel (not a silent XLA fallback)."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    return text


def test_level_tiles_distinct_and_chip_aligned():
    mm = [(t["matmul"]["bm"], t["matmul"]["bk"], t["matmul"]["bn"])
          for t in DEFAULT_LEVEL_TILES]
    assert len(mm) == cm.NUM_LEVELS
    # distinct where serving runs them: M <= 16 rows clamps bm, so the
    # (bk, bn) pairs alone must already tell the levels apart
    assert len({(bk, bn) for _, bk, bn in mm}) == cm.NUM_LEVELS
    assert all(bm % 16 == 0 and bk % 128 == 0 and bn % 128 == 0
               for bm, bk, bn in mm)
    assert all(T % bkv == 0 and bq % 8 == 0 for bq, bkv in ATTN_TILES)


@pytest.mark.parametrize("level", range(cm.NUM_LEVELS))
def test_block_matmul_level_compiles(one_chip, level):
    """starcoder2-3b MLP widths (up 3072x12288, down 12288x3072) at the
    decode (8 rows) and 16-token prefill-chunk M of serving."""
    tiles = DEFAULT_LEVEL_TILES[level]["matmul"]
    m, f = SC.d_model, SC.d_ff
    for rows in (SLOTS, 16):
        for k, n in ((m, f), (f, m)):
            text = _kernels(lambda x, w: ops.block_matmul(x, w, **tiles),
                            _sds(one_chip, (rows, k)),
                            _sds(one_chip, (k, n)))
            assert "block_matmul" in text


@pytest.mark.parametrize("tiles", ATTN_TILES,
                         ids=[f"bq{q}-bkv{kv}" for q, kv in ATTN_TILES])
@pytest.mark.parametrize("batch,seq", [(1, 16), (SLOTS, 1)],
                         ids=["prefill16", "decode"])
def test_flash_attention_compiles(one_chip, tiles, batch, seq):
    bq, bkv = tiles
    h, k, d = SC.num_heads, SC.num_kv_heads, SC.head_dim

    def attn(q, kc, vc, pos):
        return ops.flash_attention(q, kc, vc, q_positions=pos,
                                   kv_valid_len=pos[:, -1] + 1,
                                   window=SC.sliding_window, bq=bq, bkv=bkv)
    _kernels(attn, _sds(one_chip, (batch, seq, h, d)),
             _sds(one_chip, (batch, k, T, d)),
             _sds(one_chip, (batch, k, T, d)),
             _sds(one_chip, (batch, seq), jnp.int32))


def test_flash_attention_paged_compiles(one_chip):
    h, k, d = SC.num_heads, SC.num_kv_heads, SC.head_dim
    n_pages = SLOTS * T // PAGE + 1

    def attn(q, kp, vp, table, pos):
        return ops.flash_attention_paged(q, kp, vp, page_table=table,
                                         q_positions=pos,
                                         kv_valid_len=pos[:, 0] + 1,
                                         window=SC.sliding_window)
    _kernels(attn, _sds(one_chip, (SLOTS, 1, h, d)),
             _sds(one_chip, (n_pages, k, PAGE, d)),
             _sds(one_chip, (n_pages, k, PAGE, d)),
             _sds(one_chip, (SLOTS, T // PAGE), jnp.int32),
             _sds(one_chip, (SLOTS, 1), jnp.int32))


@pytest.mark.parametrize("length,chunk", [(16, 16), (64, 16), (512, 256)],
                         ids=["chunk16-L16", "chunk16-L64", "chunk256-L512"])
def test_ssd_scan_compiles(one_chip, length, chunk):
    """mamba2-780m widths: 48 heads of P=64, state N=128."""
    s = MB.ssm
    h, p, n = s.num_heads, s.head_dim, s.state_dim

    def scan(x, dt, a, b, c, h0):
        return ops.ssd_scan(x, dt, a, b, c, chunk_size=chunk,
                            initial_state=h0)
    _kernels(scan, _sds(one_chip, (1, length, h, p)),
             _sds(one_chip, (1, length, h), jnp.float32),
             _sds(one_chip, (h,), jnp.float32),
             _sds(one_chip, (1, length, h, n)),
             _sds(one_chip, (1, length, h, n)),
             _sds(one_chip, (1, h, p, n), jnp.float32))


def _stored_ops(hlo: str) -> list[tuple[str, tuple]]:
    """(op, shape) of each instruction that writes a buffer: those outside
    fusion bodies, a fusion counted as the op at its root (through
    bitcasts).  An op inside a fusion body is read by its consumer and
    stores nothing."""
    comps, roots, bodies, cur = {}, {}, set(), None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), {})
            continue
        body = re.search(r" fusion\(.*calls=%([\w.-]+)", line)
        if body:
            bodies.add(body.group(1))
        m = re.match(r"^\s*(ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* "
                     r"([\w-]+)\((.*)$", line)
        if m is None or cur is None:
            continue
        root, name, dims, op, rest = m.groups()
        cur[name] = (op, tuple(int(d) for d in dims.split(",") if d), rest)
        if root:
            roots[id(cur)] = name

    def stored(op, rest):
        if op != "fusion":
            return op
        body = comps[re.search(r"calls=%([\w.-]+)", rest).group(1)]
        op, _, rest = body[roots[id(body)]]
        while op == "bitcast":
            op, _, rest = body[re.match(r"%([\w.-]+)", rest).group(1)]
        return op

    return [(stored(op, rest), shape) for name, comp in comps.items()
            if name not in bodies for op, shape, rest in comp.values()]


@pytest.mark.parametrize("name", ["starcoder2-3b", "deepseek-v2-lite-16b"])
def test_decode_quantum_writes_stacked_cache_in_place(one_chip, name):
    """The served 16-step decode quantum, cache donated, at published
    widths and the benchmark cells' 96 slots x 1168 positions
    (DeepSeek-V2-Lite holding 8 of its 64 experts, as one chip of the
    cell's expert-parallel deployment): no copy, dynamic-update-slice or
    stored dynamic-slice of a whole stacked linear KV leaf or of one layer
    of it (each would be a pass over the whole cache every step), and
    temporaries under half the stacks' bytes (the layer scan's
    ``xs``/``ys`` form held a second copy of the cache: 4.1e9 B, 1.2x)."""
    cfg = get_config(name)
    if cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, held_experts=8))
    model = build_model(cfg)
    b, k = 96, 16

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda x: _sds(one_chip, x.shape, x.dtype), tree)
    cache = sds(abstract_params(model.cache_specs(b, 1168)))
    rows = _sds(one_chip, (b,), jnp.int32)
    compiled = jax.jit(model.decode_quantum, static_argnums=(5,),
                       donate_argnums=(2,)).lower(
        sds(abstract_params(model.param_specs())), rows, cache, rows, rows,
        k).compile()
    linear = model.paged_leaf_paths()
    flat, _ = jax.tree_util.tree_flatten_with_path(cache)
    stacks = [leaf for path, leaf in flat
              if path_keys(path)[0] == "blocks" and path_keys(path) in linear]
    shapes = {leaf.shape for leaf in stacks} | {leaf.shape[1:]
                                                for leaf in stacks}
    passes = [(op, shape) for op, shape in _stored_ops(compiled.as_text())
              if shape in shapes
              and op in ("copy", "dynamic-update-slice", "dynamic-slice")]
    assert not passes, (name, passes)
    stack_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in stacks)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < stack_bytes / 2, (name, temp, stack_bytes)
