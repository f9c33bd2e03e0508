"""hlo_cost: the trip-count-aware HLO cost model vs analytic ground truth."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_cost import analyze, parse_module


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_scan_flops_multiplied_by_trip_count():
    W = jnp.zeros((64, 64), jnp.float32)
    x = jnp.zeros((64, 64), jnp.float32)

    def scanned(w, x):
        def body(c, _):
            return w @ c, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    r = analyze(_compile_text(scanned, W, x))
    assert r["flops"] == 10 * 2 * 64 ** 3, r["flops"]
    assert 10 in r["while_trips"]
    # XLA's own cost_analysis undercounts loop bodies (the motivation)
    ca = jax.jit(scanned).lower(W, x).compile().cost_analysis()
    xla = (ca[0] if isinstance(ca, (list, tuple)) else ca)["flops"]
    assert xla < r["flops"]


def test_grad_of_scan_counts_fwd_and_bwd():
    W = jnp.zeros((32, 32), jnp.float32)
    x = jnp.zeros((32, 32), jnp.float32)

    def loss(w, x):
        def body(c, _):
            return w @ c, None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return jnp.sum(y)

    r = analyze(_compile_text(jax.grad(loss), W, x))
    # fwd (1 dot) + bwd (2 dots) per iteration
    assert r["flops"] == 7 * 3 * 2 * 32 ** 3, r["flops"]


@pytest.mark.slow
def test_collectives_inside_loops_are_scaled():
    import os
    import subprocess
    import sys
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.launch.hlo_cost import analyze
mesh = jax.make_mesh((4,), ("d",), axis_types=(AxisType.Auto,))
x = jnp.zeros((8, 64), jnp.float32)

def f(x):
    def body(c, _):
        s = jax.lax.with_sharding_constraint(c, NamedSharding(mesh, P("d")))
        r = jnp.sum(s, axis=0, keepdims=True)          # cross-shard reduce
        return c + r, None
    y, _ = jax.lax.scan(body, x, None, length=5)
    return y

t = jax.jit(f, in_shardings=NamedSharding(mesh, P("d"))).lower(x).compile().as_text()
r = analyze(t)
counts = r["collective_counts"]
assert any(v >= 5 for v in counts.values()), counts   # scaled by trip count
print("COLL_OK", counts)
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env={**os.environ, "PYTHONPATH": "src"},
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))), timeout=300)
    assert "COLL_OK" in r.stdout, r.stdout + r.stderr


def test_def_regex_tuple_and_layout_suffixed_shapes():
    """Satellite: _DEF_RE must not skip tuple results whose layouts contain
    parens (TPU tiling like T(8,128)) or dynamic-dim markers."""
    from repro.launch.hlo_cost import _DEF_RE, _PARAM_RE, _parse_shape
    cases = [
        ("  %f = (f32[8,16]{1,0:T(8,128)}, s32[8]{0}) fusion(%a), kind=kLoop",
         "f", "fusion"),
        ("  ROOT %r = f32[8,16]{1,0:T(8,128)} add(%a, %b)", "r", "add"),
        ("  %t = (f32[8,16], s32[8]) custom-call(%a)", "t", "custom-call"),
        ("  %d = s32[<=8]{0} add(%a, %b)", "d", "add"),
    ]
    for line, name, opcode in cases:
        m = _DEF_RE.match(line)
        assert m and m.group(1) == name and m.group(3) == opcode, line
    ps = _PARAM_RE.findall(
        "%p0: f32[8,16]{1,0:T(8,128)}, %p1: (f32[4]{0:T(8)}, s32[4])")
    assert ps == [("p0", "f32[8,16]{1,0:T(8,128)}"),
                  ("p1", "(f32[4]{0:T(8)}, s32[4])")], ps
    # dynamic dims parse at their bound; layout digits are not dims
    assert _parse_shape("s32[<=8]{0}") == [("s32", [8])]
    assert _parse_shape("(f32[2,3]{1,0:T(8,128)}, bf16[4])") == \
        [("f32", [2, 3]), ("bf16", [4])]


def test_collective_group_parsing_all_three_forms():
    from repro.launch.hlo_cost import collective_groups
    brace = collective_groups(
        "%x = f32[8] all-gather(%a), replica_groups={{0,4},{1,5}}")
    assert brace == [[0, 4], [1, 5]], brace
    iota = collective_groups(
        "%x = f32[8] all-reduce(%a), replica_groups=[2,4]<=[4,2]T(1,0)")
    assert iota == [[0, 2, 4, 6], [1, 3, 5, 7]], iota
    flat_iota = collective_groups(
        "%x = f32[8] all-gather(%a), replica_groups=[1,8]<=[8]")
    assert flat_iota == [[0, 1, 2, 3, 4, 5, 6, 7]], flat_iota
    pairs = collective_groups(
        "%x = f32[8] collective-permute(%a), source_target_pairs={{0,2},{2,0}}")
    assert pairs == [[0, 2], [2, 0]], pairs
    assert collective_groups("%x = f32[8] all-reduce(%a), replica_groups={}") \
        == []


def test_analyze_emits_per_op_collective_records():
    """collective_ops carries kind/bytes/wire/groups for every collective."""
    from repro.launch.hlo_cost import analyze
    hlo = """
HloModule m

ENTRY %main (p0: f32[64,64]) -> f32[64,64] {
  %p0 = f32[64,64]{1,0} parameter(0)
  %ag = f32[64,64]{1,0} all-gather(%p0), replica_groups={{0,1},{2,3}}, dimensions={0}
  ROOT %cp = f32[64,64]{1,0} collective-permute(%ag), source_target_pairs={{0,1},{1,0}}
}
"""
    r = analyze(hlo)
    ops = r["collective_ops"]
    assert [o["kind"] for o in ops] == ["all-gather", "collective-permute"]
    ag, cp = ops
    assert ag["group_size"] == 2 and ag["groups"] == [[0, 1], [2, 3]]
    assert ag["bytes"] == 64 * 64 * 4
    assert ag["wire_bytes"] == 64 * 64 * 4 / 2          # (g-1)/g of result
    assert cp["wire_bytes"] == 64 * 64 * 4              # full buffer
    assert all(o["mult"] == 1 for o in ops)


def test_parse_module_finds_entry_and_computations():
    t = _compile_text(lambda a, b: a @ b + 1.0,
                      jnp.zeros((16, 16)), jnp.zeros((16, 16)))
    comps = parse_module(t)
    assert "__entry__" in comps
    assert analyze(t)["flops"] == 2 * 16 ** 3
