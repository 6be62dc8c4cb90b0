import re

import jax
import jax.numpy as jnp

from repro.launch.hlo_analysis import analyze, op_scopes, parse_module


def _compile(f, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    return jax.jit(f).lower(*args).compile()


def test_scan_flops_scaled_by_trip_count():
    def f(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y
    c = _compile(f, (128, 128), (128, 128))
    r = analyze(c.as_text())
    assert r["dot_flops"] == 10 * 2 * 128 ** 3


def test_nested_scan_flops():
    def g(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        y, _ = jax.lax.scan(outer, x, None, length=4)
        return y
    c = _compile(g, (64, 64), (64, 64))
    r = analyze(c.as_text())
    assert r["dot_flops"] == 12 * 2 * 64 ** 3


def test_plain_dot_flops():
    c = _compile(lambda a, b: a @ b, (32, 64), (64, 16))
    r = analyze(c.as_text())
    assert r["dot_flops"] == 2 * 32 * 64 * 16


def test_parse_module_splits_computations():
    def f(x):
        def body(c, _):
            return c * 2.0, None
        y, _ = jax.lax.scan(body, x, None, length=5)
        return y
    c = _compile(f, (8,))
    comps = parse_module(c.as_text())
    assert len(comps) >= 2          # entry + loop body/cond


def test_op_scopes_of_two_named_scopes():
    def f(x, w):
        with jax.named_scope("kv.Select"):
            s = jnp.sort(x, axis=-1)
        with jax.named_scope("kv.Attend"):
            y = s @ w
        return jnp.tanh(y)            # outside any scope
    text = _compile(f, (8, 128), (128, 128)).as_text()
    scopes = op_scopes(text)
    assert set(scopes.values()) == {"kv.Select", "kv.Attend"}
    ops = dict(re.findall(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$", text,
                          re.M))
    sorts = [n for n, line in ops.items() if " sort(" in line]
    assert sorts and all(scopes[n] == "kv.Select" for n in sorts)
    assert any(v == "kv.Attend" and ("dot(" in ops[n] or "fusion(" in ops[n])
               for n, v in scopes.items())
    # an op of no scope is left out; other prefixes read nothing
    assert not any("tanh" in n for n in scopes)
    assert op_scopes(text, prefixes=("cascade.",)) == {}
