"""AST dygraph-to-static tests: Python if/while over Tensors must compile
to real XLA control flow (lax.cond / lax.while_loop), not be frozen at
trace time.

Reference strategy parity: dygraph_to_static/test_ifelse.py,
test_loop.py, test_logical.py — run the same function dygraph vs
to_static and compare.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.jit import to_static
from paddle_tpu.jit.dy2static import ast_transform, Dy2StaticError


def _branchy(x):
    if paddle.sum(x) > 0:
        y = x * 2
    else:
        y = x - 1
    return y


def test_ast_transform_produces_new_function():
    new = ast_transform(_branchy)
    assert new is not None and getattr(new, "__pt_dy2static__", False)


def test_ifelse_both_branches_one_program():
    f = to_static(_branchy)
    xp = paddle.to_tensor(np.array([1.0, 2.0], "float32"))
    xn = paddle.to_tensor(np.array([-1.0, -2.0], "float32"))
    assert np.allclose(f(xp).numpy(), [2, 4])
    # same shape signature -> same compiled program, other branch taken
    assert np.allclose(f(xn).numpy(), [-2, -3])
    assert len(f._cache) == 1


def _loopy(x):
    s = paddle.zeros([])
    i = paddle.zeros([])
    while i < x:
        s = s + i
        i = i + 1
    return s


def test_while_loop_data_dependent_trip_count():
    g = to_static(_loopy)
    assert float(g(paddle.to_tensor(np.array(5.0, "float32"))).numpy()) == 10.0
    # different trip count through the SAME compiled program
    assert float(g(paddle.to_tensor(np.array(3.0, "float32"))).numpy()) == 3.0
    assert len(g._cache) == 1


def _boolop(x):
    if (paddle.sum(x) > 0) and (paddle.max(x) < 10):
        y = x + 1
    else:
        y = x - 1
    return y


def test_logical_and_on_tensors():
    f = to_static(_boolop)
    x1 = paddle.to_tensor(np.array([1.0, 2.0], "float32"))
    x2 = paddle.to_tensor(np.array([1.0, 20.0], "float32"))
    assert np.allclose(f(x1).numpy(), [2, 3])
    assert np.allclose(f(x2).numpy(), [0, 19])


def _grad_branch(x):
    if paddle.sum(x) > 0:
        y = x * 3
    else:
        y = x * 5
    return paddle.sum(y * y)


def test_gradient_through_converted_ifelse():
    f = to_static(_grad_branch)
    xt = paddle.to_tensor(np.array([1.0, -0.5], "float32"),
                          stop_gradient=False)
    f(xt).backward()
    assert np.allclose(xt.grad.numpy(), 18 * np.array([1.0, -0.5]),
                       atol=1e-5)
    # negative branch gradient: 2*25*x = 50x
    xt2 = paddle.to_tensor(np.array([-1.0, -0.5], "float32"),
                           stop_gradient=False)
    f(xt2).backward()
    assert np.allclose(xt2.grad.numpy(), 50 * np.array([-1.0, -0.5]),
                       atol=1e-5)


def _python_if(x, flag):
    if flag:                     # plain Python condition stays Python
        return x + 1
    return x - 1


def test_python_condition_untouched():
    f = to_static(_python_if)
    x = paddle.to_tensor(np.array([1.0], "float32"))
    assert float(f(x, True).numpy()[0]) == 2.0
    assert float(f(x, False).numpy()[0]) == 0.0


def _early_return_vec(x):
    if paddle.sum(x) > 0:
        return x * 2
    return x


def test_early_return_now_transforms():
    # round 2 left returns as Python semantics (this test asserted a raise);
    # the return transformer now carries them through lax.cond
    f = to_static(_early_return_vec)
    assert float(f(paddle.to_tensor(np.array([1.0], "float32")))
                 .numpy()[0]) == 2.0
    assert float(f(paddle.to_tensor(np.array([-1.0], "float32")))
                 .numpy()[0]) == -1.0


def _nested(x):
    if paddle.sum(x) > 0:
        if paddle.max(x) > 5:
            y = x * 10
        else:
            y = x * 2
    else:
        y = -x
    return y


def test_nested_ifelse():
    f = to_static(_nested)
    a = paddle.to_tensor(np.array([1.0, 6.0], "float32"))
    b = paddle.to_tensor(np.array([1.0, 2.0], "float32"))
    c = paddle.to_tensor(np.array([-1.0, -2.0], "float32"))
    assert np.allclose(f(a).numpy(), [10, 60])
    assert np.allclose(f(b).numpy(), [2, 4])
    assert np.allclose(f(c).numpy(), [1, 2])


class _CondLayer(paddle.nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = paddle.nn.Linear(4, 4)

    def forward(self, x):
        h = self.fc(x)
        if paddle.mean(h) > 0:
            out = h * 2
        else:
            out = h * 0.5
        return out


def test_layer_method_conversion():
    paddle.seed(11)
    layer = _CondLayer()
    x = paddle.to_tensor(np.random.RandomState(0)
                         .randn(2, 4).astype("float32"))
    eager = layer(x).numpy()
    to_static(layer)
    static = layer.forward(x).numpy()
    assert np.allclose(eager, static, atol=1e-5)


def _uninit(x):
    if paddle.sum(x) > 0:
        z = x * 2
    else:
        z = x * 3
    return z


def test_branch_defined_var_works():
    # z first bound inside the branches (the common pattern)
    f = to_static(_uninit)
    out = f(paddle.to_tensor(np.array([2.0], "float32")))
    assert float(out.numpy()[0]) == 4.0


# -- for / break / continue / return transforms (loop_transformer.py,
# break_continue_transformer.py, return_transformer.py parity) ---------------

def _for_range_tensor(x, n):
    s = paddle.zeros([])
    for i in range(n):
        s = s + x * i.astype("float32")
    return s


def test_for_over_tensor_range_compiles_to_while():
    f = to_static(_for_range_tensor)
    x = paddle.to_tensor(np.array(2.0, "float32"))
    assert float(f(x, paddle.to_tensor(np.array(4))).numpy()) == 12.0
    # data-dependent trip count through the SAME compiled program
    assert float(f(x, paddle.to_tensor(np.array(3))).numpy()) == 6.0
    assert len(f._cache) == 1


def _for_static_range(x):
    s = paddle.zeros([])
    for i in range(3):
        s = s + x * i
    return s


def test_for_over_python_range():
    f = to_static(_for_static_range)
    assert float(f(paddle.to_tensor(np.array(2.0, "float32"))).numpy()) == 6.0


def _for_tensor_rows(x):
    s = paddle.zeros([3])
    for row in x:
        s = s + row
    return s


def test_for_over_tensor_rows():
    f = to_static(_for_tensor_rows)
    assert np.allclose(f(paddle.ones([4, 3])).numpy(), [4, 4, 4])


def _early_return(x):
    if paddle.sum(x) > 0:
        return x * 2
    return x * 3


def test_early_return_traced_pred():
    f = to_static(_early_return)
    pos = paddle.to_tensor(np.array(1.0, "float32"))
    neg = paddle.to_tensor(np.array(-1.0, "float32"))
    assert float(f(pos).numpy()) == 2.0
    assert float(f(neg).numpy()) == -3.0
    assert len(f._cache) == 1


def _tensor_break(x):
    i = paddle.zeros([], dtype="int32")
    s = paddle.zeros([])
    while i < 100:
        s = s + x
        if s > 5:
            break
        i = i + 1
    return s


def test_tensor_break_in_tensor_while():
    f = to_static(_tensor_break)
    assert float(f(paddle.to_tensor(np.array(2.0, "float32"))).numpy()) == 6.0


def _tensor_continue(x, n):
    s = paddle.zeros([])
    for i in range(n):
        if paddle.mod(i, paddle.to_tensor(np.array(2))) == 0:
            continue
        s = s + x * i.astype("float32")
    return s


def test_tensor_continue_in_for():
    f = to_static(_tensor_continue)
    out = f(paddle.to_tensor(np.array(1.0, "float32")),
            paddle.to_tensor(np.array(6)))
    assert float(out.numpy()) == 9.0      # 1 + 3 + 5


def _return_inside_loop(x):
    i = paddle.zeros([], dtype="int32")
    while i < 100:
        if x * i.astype("float32") > 4:
            return i
        i = i + 1
    return i


def test_return_inside_tensor_loop():
    f = to_static(_return_inside_loop)
    assert int(f(paddle.to_tensor(np.array(1.5, "float32"))).numpy()) == 3


def _py_bound_tensor_break(x):
    s = paddle.zeros([])
    for i in range(100):
        s = s + x
        if s > 5:
            break
    return s


def test_tensor_break_in_python_loop_raises():
    """A Tensor break cannot retroactively convert a Python-bound loop:
    must raise loudly (never silently trace wrong)."""
    f = to_static(_py_bound_tensor_break)
    with pytest.raises(Exception) as ei:
        f(paddle.to_tensor(np.array(2.0, "float32")))
    assert "tensor-dependent" in str(ei.value) or \
        "Dy2Static" in type(ei.value).__name__


def _break_continue_mixed(x, n):
    """break + continue + nested if in one loop."""
    s = paddle.zeros([])
    for i in range(n):
        f = i.astype("float32")
        if paddle.mod(i, paddle.to_tensor(np.array(2))) == 0:
            continue
        s = s + x * f
        if s > 10:
            break
    return s


def test_break_continue_mixed_matches_python():
    f = to_static(_break_continue_mixed)
    # python semantics: i=1 s=2, i=3 s=8, i=5 s=18 -> break
    out = f(paddle.to_tensor(np.array(2.0, "float32")),
            paddle.to_tensor(np.array(10)))
    assert float(out.numpy()) == 18.0


class _LoopNet(paddle.nn.Layer):
    def __init__(self):
        super().__init__()
        self.lin = paddle.nn.Linear(4, 4)

    def forward(self, x, steps):
        h = x
        for _ in range(steps):
            h = paddle.tanh(self.lin(h))
        return h


def test_layer_for_loop_dygraph_equals_static():
    paddle.seed(7)
    net = _LoopNet()
    xs = paddle.to_tensor(np.random.RandomState(0)
                          .randn(2, 4).astype("float32"))
    dy = net(xs, 3).numpy()
    st = to_static(net)(xs, 3).numpy()
    assert np.allclose(dy, st, atol=1e-5)


class _Ctx:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def _return_under_with(x):
    with _Ctx():
        if paddle.sum(x) > 0:
            return x * 2
        return x * 3


def test_return_inside_with_guarded():
    f = to_static(_return_under_with)
    assert float(f(paddle.to_tensor(np.array(1.0, "float32")))
                 .numpy()) == 2.0
    assert float(f(paddle.to_tensor(np.array(-1.0, "float32")))
                 .numpy()) == -3.0


def _return_under_try(x):
    try:
        if paddle.sum(x) > 0:
            return x * 2
        return x * 3
    finally:
        pass


def test_return_inside_try_guarded():
    f = to_static(_return_under_try)
    assert float(f(paddle.to_tensor(np.array(1.0, "float32")))
                 .numpy()) == 2.0


def _for_else_return(x):
    for _ in range(3):
        x = x + 1
    else:
        return x * 2
    return x


def test_for_else_return_transforms_cleanly():
    # the return in the orelse must NOT emit a loop break (SyntaxError would
    # silently disable the whole transform)
    from paddle_tpu.jit.dy2static import ast_transform
    g = ast_transform(_for_else_return)
    assert g is not None
    assert float(g(paddle.to_tensor(np.array(1.0, "float32")))
                 .numpy()) == 8.0


def _while_else_break(x, trip):
    i = 0
    while i < 3:
        if i == trip:
            break
        i += 1
    else:
        x = x * 10
    return x


def test_while_else_preserved_with_break():
    from paddle_tpu.jit.dy2static import ast_transform
    g = ast_transform(_while_else_break)
    x = paddle.to_tensor(np.array(1.0, "float32"))
    # break taken -> else skipped
    assert float(g(x, 1).numpy()) == 1.0
    # no break -> else runs
    assert float(g(x, 99).numpy()) == 10.0


def _gen_loop(x):
    def gen():
        for i in range(1000000000):      # effectively infinite if listed
            yield i
    s = x
    for v in gen():
        s = s + 1
        if v >= 2:
            break
    return s


def test_generator_iterable_stays_lazy():
    """A generator iterable must NOT be materialized by the for-lowering
    (a DataLoader or itertools.count would hang)."""
    from paddle_tpu.jit.dy2static import ast_transform
    g = ast_transform(_gen_loop)
    out = g(paddle.to_tensor(np.array(0.0, "float32")))
    assert float(out.numpy()) == 3.0


def _dict_loop(x, d):
    s = x
    for k in d:
        s = s + d[k]
    return s


def test_for_over_dict_iterates_keys():
    """Mappings iterate by key: must NOT take the indexed-while lowering
    (dict[0] is not dict-iteration)."""
    from paddle_tpu.jit.dy2static import ast_transform
    g = ast_transform(_dict_loop)
    out = g(paddle.to_tensor(np.array(0.0, "float32")),
            {"a": 1.0, "b": 2.0})
    assert float(out.numpy()) == 3.0


def _gen_with_while(x):
    def gen():
        i = 0
        while i < 5:
            yield i
            i += 1
    s = x
    for v in gen():
        s = s + v
    return s


def test_generator_with_while_body_not_converted():
    """A nested generator's while must keep Python semantics — converting
    it would make the body a generator function that never runs."""
    from paddle_tpu.jit.dy2static import ast_transform
    g = ast_transform(_gen_with_while)
    out = g(paddle.to_tensor(np.array(0.0, "float32")))
    assert float(out.numpy()) == 10.0


def _closure_with_tensor_while(x):
    def helper(v):
        i = paddle.zeros([], dtype="int32")
        while i < v.astype("int32"):
            i = i + 1
        return i
    return helper(x) * 2


def test_nested_closure_control_flow_still_converts():
    """Non-generator nested defs keep getting their tensor control flow
    converted (only generator defs are skipped)."""
    f = to_static(_closure_with_tensor_while)
    out = f(paddle.to_tensor(np.array(3.0, "float32")))
    assert int(out.numpy()) == 6


def test_lazyseq_evicts_consumed_prefix():
    from paddle_tpu.jit.dy2static import _LazySeq
    s = _LazySeq(iter(range(1000)))
    for i in range(1000):
        assert s.get(i) == i
        assert len(s._buf) <= 2      # O(1) window, not the whole stream


# -- assert / print / cast transformers (VERDICT r4 item 6) ------------------

def test_assert_in_graph_passes_and_fails():
    """assert_transformer parity: the assert lives IN the compiled graph
    and fires on the runtime value."""
    @to_static
    def f(x):
        assert paddle.sum(x) > 0, "sum must be positive"
        return x * 2

    x = paddle.to_tensor(np.ones(3, np.float32))
    np.testing.assert_allclose(f(x).numpy(), 2 * np.ones(3))
    with pytest.raises(Exception, match="sum must be positive"):
        out = f(paddle.to_tensor(-np.ones(3, np.float32)))
        np.asarray(out.numpy())    # force execution

def test_print_traced_intermediate(capfd):
    """print_transformer parity: printing inside @to_static shows the
    RUNTIME value, not a tracer repr."""
    @to_static
    def f(x):
        y = x + 1
        print("y is", y)
        return y

    out = f(paddle.to_tensor(np.float32(41.0)))
    float(out)                         # sync so the callback flushes
    captured = capfd.readouterr()
    assert "42" in captured.out
    assert "Traced" not in captured.out


def test_cast_int_float_bool_on_tensor():
    """cast_transformer parity: int/float/bool on tensors become dtype
    casts instead of concretization errors."""
    @to_static
    def f(x):
        a = int(x)            # -> int64 cast
        b = float(a)          # -> float32 cast
        c = bool(x - x)       # -> bool cast (all False)
        return a, b, c

    a, b, c = f(paddle.to_tensor(np.float32(3.7)))
    assert "int" in str(a.dtype)      # int64 (int32 when x64 is off)
    assert int(a.numpy()) == 3
    assert float(b) == 3.0
    assert str(c.numpy().dtype) == "bool" and not bool(c.numpy())
    # eager python values keep python semantics
    @to_static
    def g(n):
        return int(n) + 1
    assert g(3.9) == 4


def test_generator_reports_unsupported_syntax():
    from paddle_tpu.jit.dy2static import Dy2StaticError

    def gen(x):
        for i in range(3):
            yield x + i

    with pytest.raises(Dy2StaticError, match="generator.*yield"):
        to_static(gen)(paddle.to_tensor(1.0))


def test_unconvertible_dynamic_loop_reports_guidance():
    """A while with a data-dependent condition that stays Python (break
    escape) must raise the guided diagnostic, not a bare tracer error."""
    from paddle_tpu.jit.dy2static import Dy2StaticError

    @to_static
    def f(x):
        while paddle.sum(x) < 100:    # while..else stays Python
            x = x * 2
        else:
            x = x + 1
        return x

    with pytest.raises(Dy2StaticError, match="data-dependent"):
        f(paddle.to_tensor(np.ones(3, np.float32)))


def test_print_sep_end_file_and_braces(tmp_path):
    """The traced print path must honor sep/end/file and survive brace
    characters (it routes through builtin print in a host callback, not a
    format string)."""
    import io
    import sys as _sys

    @to_static
    def f(x):
        import sys
        y = x + 1
        print("y{", y, sep="{", end="!", file=sys.stderr)
        return y

    err = io.StringIO()
    old = _sys.stderr
    try:
        _sys.stderr = err
        out = f(paddle.to_tensor(np.float32(41.0)))
        float(out)
    finally:
        _sys.stderr = old
    s = err.getvalue()
    assert "42" in s and s.endswith("!"), repr(s)


def test_bare_assert_failure_message():
    @to_static
    def g(n):
        assert n > 5
        return n

    with pytest.raises(AssertionError) as ei:
        g(3)
    assert "None" not in str(ei.value)


def test_assert_fallback_without_host_callbacks(monkeypatch):
    """ADVICE r4: on callback-less backends the
    assert condition rides out of the compiled program as a fetched flag
    and still raises host-side — instead of warn-and-skip."""
    from paddle_tpu.jit import dy2static as d
    monkeypatch.setattr(d, "_host_callbacks_supported", lambda: False)

    @to_static
    def f(x):
        assert paddle.sum(x) > 0, "sum must be positive"
        return x * 2

    x = paddle.to_tensor(np.ones(3, np.float32))
    np.testing.assert_allclose(f(x).numpy(), 2 * np.ones(3))
    with pytest.raises(AssertionError, match="sum must be positive"):
        f(paddle.to_tensor(-np.ones(3, np.float32)))

    # gradients still flow through the value outputs with flags attached
    @to_static
    def g(x):
        assert paddle.sum(x) < 100
        return (x * 3).sum()

    t = paddle.to_tensor(np.ones(3, np.float32))
    t.stop_gradient = False
    g(t).backward()
    np.testing.assert_allclose(t.grad.numpy(), 3 * np.ones(3))

    # nested @to_static: the inner flag is traced inside the outer trace;
    # it must propagate to the OUTER frame and still fire host-side
    @to_static
    def inner(x):
        assert paddle.sum(x) > 0, "inner positive"
        return x + 1

    @to_static
    def outer(x):
        return inner(x) * 2

    np.testing.assert_allclose(
        outer(paddle.to_tensor(np.ones(3, np.float32))).numpy(),
        4 * np.ones(3))
    with pytest.raises(AssertionError, match="inner positive"):
        outer(paddle.to_tensor(-np.ones(3, np.float32)))


# -- list / TensorArray transformer (VERDICT r4 #7) ---------------------------

def test_list_append_in_traced_for():
    """Appends inside a Tensor-bounded loop lower to the BoundedTensorArray
    carry (list_transformer.py parity); the stacked valid prefix equals
    the dygraph python-list result."""
    def f(x, n):
        l = []
        i = 0
        while i < n:
            l.append(x[i] * (i + 1))
            i += 1
        return paddle.stack(l), len(l)

    x = paddle.to_tensor(np.arange(12, dtype=np.float32).reshape(6, 2))
    # dygraph: plain python loop, plain list
    want_stack, want_len = f(x, 4)
    sf = to_static(f)
    got_stack, got_len = sf(x, paddle.to_tensor(4))
    assert int(got_len.numpy()) == want_len == 4
    np.testing.assert_allclose(got_stack.numpy()[:4], want_stack.numpy())
    # different n, same compiled program (shape-stable: capacity-padded)
    got2, len2 = sf(x, paddle.to_tensor(6))
    np.testing.assert_allclose(got2.numpy()[:6], f(x, 6)[0].numpy())
    assert int(len2.numpy()) == 6


def test_list_append_under_traced_if():
    """Appends under a Tensor `if` inside the loop: the no-append arm
    carries the same-typed array; count and values match dygraph."""
    def f(x, n):
        l = []
        i = 0
        while i < n:
            if x[i] > 0:
                l.append(x[i] * 2)
            i += 1
        return paddle.stack(l), len(l)

    xv = np.array([1.0, -2.0, 3.0, -4.0, 5.0], np.float32)
    x = paddle.to_tensor(xv)
    want_stack, want_len = f(x, 5)
    got_stack, got_len = to_static(f)(x, paddle.to_tensor(5))
    assert int(got_len.numpy()) == want_len == 3
    np.testing.assert_allclose(got_stack.numpy()[:3], want_stack.numpy())


def test_list_readback_and_indexing_after_loop():
    """Read-back forms after the loop: indexing, len, concat."""
    def f(x, n):
        l = []
        i = 0
        while i < n:
            l.append(paddle.reshape(x[i] + i, [1]))
            i += 1
        first = l[0]
        last = l[len(l) - 1]
        return paddle.concat(l), first, last

    x = paddle.to_tensor(np.arange(8, dtype=np.float32))
    want_cat, want_first, want_last = f(x, 3)
    got_cat, got_first, got_last = to_static(f)(x, paddle.to_tensor(3))
    np.testing.assert_allclose(got_cat.numpy()[:3], want_cat.numpy())
    np.testing.assert_allclose(got_first.numpy(), want_first.numpy())
    np.testing.assert_allclose(got_last.numpy(), want_last.numpy())


def test_list_nonempty_seed_and_eager_lists_unchanged():
    """A pre-seeded list promotes with its contents; appends outside any
    traced region keep plain python-list semantics."""
    def f(x, n):
        l = [x[0], x[1]]
        i = 0
        while i < n:
            l.append(x[i] + 100)
            i += 1
        return paddle.stack(l), len(l)

    x = paddle.to_tensor(np.arange(5, dtype=np.float32))
    want_stack, want_len = f(x, 2)
    got_stack, got_len = to_static(f)(x, paddle.to_tensor(2))
    assert int(got_len.numpy()) == want_len == 4
    np.testing.assert_allclose(got_stack.numpy()[:4], want_stack.numpy())

    # eager path: no traced condition -> plain python list survives
    def g(x):
        l = []
        for i in range(3):        # python range: not traced
            l.append(x + i)
        return l

    out = to_static(g)(paddle.to_tensor(np.float32(1.0)))
    assert isinstance(out, (list, tuple)) and len(out) == 3


def test_list_capacity_budget():
    from paddle_tpu.jit import (set_tensor_array_capacity,
                                get_tensor_array_capacity)
    old = get_tensor_array_capacity()
    try:
        set_tensor_array_capacity(8)

        def f(x, n):
            l = []
            i = 0
            while i < n:
                l.append(x * i)
                i += 1
            return paddle.stack(l)

        out = to_static(f)(paddle.to_tensor(np.float32(2.0)),
                           paddle.to_tensor(5))
        assert out.shape[0] == 8          # capacity-padded buffer
    finally:
        set_tensor_array_capacity(old)


def test_list_negative_index_and_capacity_overflow_raises():
    """Review regressions: l[-1] counts from the live size; appends past
    the capacity budget raise host-side through the fetched-assert
    channel instead of silently overwriting the last slot."""
    def f(x, n):
        l = []
        i = 0
        while i < n:
            l.append(x[i])
            i += 1
        return l[-1], len(l)

    x = paddle.to_tensor(np.arange(10, dtype=np.float32))
    last, ln = to_static(f)(x, paddle.to_tensor(4))
    assert float(last.numpy()) == 3.0 and int(ln.numpy()) == 4

    from paddle_tpu.jit import (set_tensor_array_capacity,
                                get_tensor_array_capacity)
    old = get_tensor_array_capacity()
    try:
        set_tensor_array_capacity(4)
        # exactly at capacity: fine
        _, ln2 = to_static(f)(x, paddle.to_tensor(4))
        assert int(ln2.numpy()) == 4
        # past capacity: host-side raise, not a silent overwrite
        with pytest.raises(AssertionError, match="tensor array capacity"):
            to_static(f)(x, paddle.to_tensor(7))
    finally:
        set_tensor_array_capacity(old)


# -- traced-bound slicing (VERDICT r5 #6: slice_op.cc StartsTensor) -----------

def test_sliding_window_traced_start():
    """Loop-carried sliding window: x[i:i+k] with a traced i lowers to
    lax.dynamic_slice (static extent, runtime start)."""
    def f(x):
        acc = paddle.zeros([4])
        i = paddle.to_tensor(0)
        n = x.shape[0]
        while i <= n - 4:
            acc = acc + x[i:i+4]
            i = i + 1
        return acc

    x = paddle.to_tensor(np.arange(10, dtype=np.float32))
    got = np.asarray(to_static(f)(x).numpy())
    want = sum(np.arange(10.)[i:i + 4] for i in range(7))
    np.testing.assert_allclose(got, want)


def test_backward_window_traced_stop():
    """x[i-k:i] — the bound pair recognized from the upper side."""
    def f(x):
        acc = paddle.zeros([3])
        i = paddle.to_tensor(3)
        while i <= x.shape[0]:
            acc = acc + x[i-3:i]
            i = i + 1
        return acc

    x = paddle.to_tensor(np.arange(10, dtype=np.float32))
    got = np.asarray(to_static(f)(x).numpy())
    want = sum(np.arange(10.)[i - 3:i] for i in range(3, 11))
    np.testing.assert_allclose(got, want)


def test_static_slices_keep_python_semantics():
    """The slice converter must round-trip non-traced bounds untouched —
    including python-list slicing and stepped tensor slices."""
    def f(x):
        a = x[1:5]
        b = x[0:8:2]
        lst = [1, 2, 3, 4]
        c = lst[1:3]
        return a.sum() + b.sum() + float(sum(c))

    x = paddle.to_tensor(np.arange(10, dtype=np.float32))
    got = float(to_static(f)(x).numpy())
    want = np.arange(10.)[1:5].sum() + np.arange(10.)[0:8:2].sum() + 5.0
    assert abs(got - want) < 1e-5


def test_setitem_slice_traced_start():
    """x[i:i+k] = v with traced i lowers to lax.dynamic_update_slice via
    the functional-rebind converter."""
    def f(x):
        i = paddle.to_tensor(2)
        while i < 6:
            x[i:i+2] = 0.0
            i = i + 2
        return x

    got = np.asarray(
        to_static(f)(paddle.to_tensor(np.arange(8, dtype=np.float32)))
        .numpy())
    want = np.arange(8.)
    want[2:4] = 0.0
    want[4:6] = 0.0
    np.testing.assert_allclose(got, want)


def test_scalar_traced_index_via_dynamic_slice():
    """x[i] with a traced scalar i takes the dynamic_index path (the VJP
    is a dynamic_update_slice, not a scatter) and matches the eager sum."""
    def f(x):
        acc = paddle.zeros([])
        i = paddle.to_tensor(0)
        while i < x.shape[0]:
            acc = acc + x[i]
            i = i + 1
        return acc

    x = paddle.to_tensor(np.arange(10, dtype=np.float32))
    assert abs(float(to_static(f)(x).numpy()) - 45.0) < 1e-5


def test_traced_slice_without_static_size_raises():
    """x[0:i] has no static extent — the converter must raise the guided
    Dy2StaticError, not a raw tracer error."""
    from paddle_tpu.jit.dy2static import Dy2StaticError

    def f(x):
        i = paddle.to_tensor(2)
        while i < 4:
            y = x[0:i]
            i = i + y.shape[0]
        return i

    with pytest.raises(Dy2StaticError, match="window size"):
        to_static(f)(paddle.to_tensor(np.arange(8, dtype=np.float32)))


def test_dynamic_slice_functional():
    """ops.manipulation.dynamic_slice — StartsTensor parity surface, with
    gradient through the window."""
    x = paddle.to_tensor(np.arange(8, dtype=np.float32))
    x.stop_gradient = False
    w = paddle.dynamic_slice(x, paddle.to_tensor(3), 2)
    np.testing.assert_allclose(np.asarray(w.numpy()), [3.0, 4.0])
    w.sum().backward()
    np.testing.assert_allclose(np.asarray(x.grad.numpy()),
                               [0, 0, 0, 1, 1, 0, 0, 0])
    y = paddle.dynamic_update_slice(
        paddle.to_tensor(np.zeros(5, np.float32)),
        paddle.to_tensor(np.ones(2, np.float32)), paddle.to_tensor(1))
    np.testing.assert_allclose(np.asarray(y.numpy()), [0, 1, 1, 0, 0])
