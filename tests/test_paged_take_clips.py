"""No served program FILLS what it takes through a block table.

``jnp.take``'s default mode fills what an out-of-range id would name: XLA
emits, behind the gather, a ``select`` over every gathered byte. In the
decode step that select was seven tenths of the program (PERF.md, PR 41). A
table's ids are in range by construction (``tests/test_paged_kv.py`` holds the
engine to that), so every take through one clips and pays for the gather
alone. Each paged form is lowered here on the CPU, at a small geometry, and
held to two things: every gather from a pool leaf clips (the jaxpr), and
no ``select`` has a gathered shape (the lowered text), so that a later
default cannot bring the select back unseen.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.lax import GatherScatterMode

from bigdl_tpu.nn.attention import (_attend_key_blocks, _kv_buffers,
                                    _write_kv_paged)
from bigdl_tpu.nn.sparse_attention import BlockSparseAttention

# a page count no other dimension has: a pool leaf is what leads with it
PAGES, PS, B, TLEN = 37, 4, 3, 10
H, H_KV, D = 4, 2, 8


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _abstract(tree):
    return jax.tree.map(lambda a: _sds(a.shape, a.dtype), tree)


def _pool(kv_dtype):
    """The float pair, or the int8 4-tuple with its float32 sidecars."""
    return _abstract(_kv_buffers((PAGES, PS, H_KV * D), (PAGES, PS, H_KV),
                                 jnp.bfloat16, None, kv_dtype))


TABLES = _sds((B, TLEN), jnp.int32)


def _step_form(kv_dtype, rows):
    def fn(pool, k_t, v_t, tables, pos):
        return _write_kv_paged(pool, k_t, v_t, tables, pos[:, None],
                               rows=rows)
    kv = _sds((B, H_KV, 1, D), jnp.bfloat16)
    return fn, (_pool(kv_dtype), kv, kv, TABLES, _sds((B,), jnp.int32))


def _chunk_form(kv_dtype):
    t = 2 * PS
    return _attend_key_blocks, (
        _sds((B, H, t, D), jnp.bfloat16), _pool(kv_dtype), TABLES,
        _sds((B, t), jnp.int32))


def _sparse_form(chunk):
    """The block-sparse layer: one K and one V leaf a group and the
    compressed keys. ``dense_len`` 16 is 2 blocks where ``topk`` is 3 of
    the table's 5, so the step's second gather (the further pages of a
    row under ``dense_len``) is not traced; with ``dense_len`` 40 it
    is."""
    m = BlockSparseAttention(16, H, H_KV, D, kernel_size=2 * PS,
                             kernel_stride=PS, block_size=2 * PS, topk=3,
                             init_blocks=1, window_size=PS,
                             dense_len=16 if chunk else 40)
    m.evaluate()
    pool = _abstract(m.init_page_pool(PAGES, PS, jnp.bfloat16))
    pos = _sds((B,), jnp.int32)
    if chunk:
        return m.forward_chunk_paged, (
            _sds((B, 2 * PS, 16), jnp.float32), pool, TABLES, pos)
    return m.forward_step_paged, (_sds((B, 16), jnp.float32), pool, TABLES,
                                  pos)


FORMS = {
    "step_rows_float": lambda: _step_form(None, True),
    "step_heads_float": lambda: _step_form(None, False),
    "step_rows_int8": lambda: _step_form("int8", True),
    "step_heads_int8": lambda: _step_form("int8", False),
    "chunk_blocks_float": lambda: _chunk_form(None),
    "chunk_blocks_int8": lambda: _chunk_form("int8"),
    "sparse_step": lambda: _sparse_form(False),
    "sparse_chunk": lambda: _sparse_form(True),
}
# gathers from a pool leaf each form traces: one a leaf, except that the
# sparse step reads each K leaf's span and gathers each group's K and V
# for every row and again for the rows under dense_len (2 + 4 + 4 + ck),
# and the sparse chunk reads each K leaf's spans beside its key blocks
# (2 + 4 + ck)
GATHERS = {"step_rows_float": 2, "step_heads_float": 2, "step_rows_int8": 4,
           "step_heads_int8": 4, "chunk_blocks_float": 2,
           "chunk_blocks_int8": 4, "sparse_step": 11, "sparse_chunk": 7}


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def _page_gathers(fn, args):
    return [e for e in _equations(jax.make_jaxpr(fn)(*args).jaxpr)
            if e.primitive.name == "gather"
            and e.invars[0].aval.shape[0] == PAGES]


def _selects_of_gathered_shape(text):
    """Lowered ``select``s whose operands have a shape some gather from a
    pool leaf returns."""
    gathered = {m.group(1) for m in re.finditer(
        rf'"stablehlo\.gather"\([^)]*\).*: \(tensor<{PAGES}x[^>]*>, '
        r'tensor<[^>]*>\) -> tensor<([^>]*)>', text)}
    assert gathered, "no gather from a pool leaf in the lowered text"
    return [m.group(0) for m in re.finditer(
        r'stablehlo\.select .*: tensor<[^>]*xi1>, tensor<([^>]*)>', text)
        if m.group(1) in gathered]


@pytest.mark.parametrize("form", sorted(FORMS))
def test_a_take_through_a_block_table_clips_and_nothing_selects(form):
    fn, args = FORMS[form]()
    gathers = _page_gathers(fn, args)
    assert len(gathers) == GATHERS[form]
    assert {e.params["mode"] for e in gathers} == {GatherScatterMode.CLIP}
    leaves = {(a.shape, a.dtype) for a in jax.tree.leaves(args)
              if a.shape[0] == PAGES}
    assert {(e.invars[0].aval.shape, e.invars[0].aval.dtype)
            for e in gathers} == leaves
    assert _selects_of_gathered_shape(jax.jit(fn).lower(*args).as_text()) \
        == []


def test_the_check_sees_a_take_that_fills():
    """The two readers above find the default mode's gather and its select:
    what they hold the forms to is what a fill would break."""
    def fn(leaf, tables):
        return jnp.take(leaf, tables, axis=0)

    args = (_pool(None)[0], TABLES)
    assert {e.params["mode"] for e in _page_gathers(fn, args)} \
        == {GatherScatterMode.FILL_OR_DROP}
    assert _selects_of_gathered_shape(jax.jit(fn).lower(*args).as_text())
