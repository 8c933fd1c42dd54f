"""Property tests for the analytic models the perf gate trusts: the
block-refetch traffic model (tune.measure.conv_traffic), the band working
set (core.blocking.conv_working_set), and the roofline cost functions
(launch.roofline) — plus the stable-key contracts the perfci extractors
join on, and the depth-first chain pricing (chain_traffic / chain_roofline)
whose fallback rule makes "fused <= unfused" true on every shape."""
from hypothesis import given, settings, strategies as st

from repro.core.blocking import ConvBlocking, conv_working_set
from repro.launch.roofline import (CHAIN_ROOFLINE_KEYS,
                                   COMPOSITE_ROOFLINE_KEYS,
                                   KERNEL_ROOFLINE_KEYS, chain_roofline,
                                   composite_roofline, kernel_roofline)
from repro.tune.measure import (CHAIN_TRAFFIC_KEYS, CONV_TRAFFIC_KEYS,
                                chain_traffic, conv_traffic)

_shapes = st.tuples(
    st.integers(7, 28),            # h == w
    st.sampled_from([32, 64, 96]),  # c
    st.sampled_from([32, 64, 128]),  # k
    st.sampled_from([(1, 0), (3, 1)]),  # (r, padding)
    st.integers(1, 2),             # stride
)


def _shape(h, c, k, rs_pad, stride):
    r, pad = rs_pad
    return {"h": h, "w": h, "c": c, "k": k, "r": r, "s": r,
            "stride": stride, "padding": pad}


def _blk(shape):
    return ConvBlocking(rb_p=2, k_blk=min(shape["k"], 64),
                        c_blk=min(shape["c"], 32), order="nkpc",
                        vmem_bytes=0)


@settings(max_examples=25)
@given(_shapes, st.integers(1, 4))
def test_traffic_nondecreasing_in_minibatch(draw, n):
    shape = _shape(*draw)
    blk = _blk(shape)
    small = conv_traffic(shape, blk, minibatch=n)
    big = conv_traffic(shape, blk, minibatch=n + 1)
    assert big["hbm_bytes"] >= small["hbm_bytes"]
    assert big["flops"] > small["flops"]
    assert big["n_steps"] >= small["n_steps"]


@settings(max_examples=25)
@given(_shapes, st.sampled_from(["fwd", "wu"]),
       st.booleans())
def test_traffic_nondecreasing_in_plane_size(draw, kind, whole_plane):
    """More pixels never means less modeled work, whatever the schedule."""
    shape = _shape(*draw)
    blk = _blk(shape)
    bigger = dict(shape, h=shape["h"] + 7, w=shape["w"] + 7)
    t0 = conv_traffic(shape, blk, kind=kind, whole_plane=whole_plane)
    t1 = conv_traffic(bigger, blk, kind=kind, whole_plane=whole_plane)
    assert t1["hbm_bytes"] >= t0["hbm_bytes"]
    assert t1["flops"] > t0["flops"]


@settings(max_examples=25)
@given(_shapes, st.integers(1, 4), st.sampled_from(["fwd", "wu", "q8"]))
def test_band_working_set_independent_of_plane(draw, rb_p, kind):
    """The §II-B claim the tiling rests on: for a fixed (rb_p, c_blk)
    band of full-width rows, per-step VMEM is the same for a 7-row and a
    224-row image of the same width — only the whole-plane legacy schedule
    scales with H*W."""
    shape = _shape(*draw)
    kw = dict(c=shape["c"], k_blk=64, r=shape["r"], s=shape["s"],
              rb_p=rb_p, c_blk=32, padding=shape["padding"],
              stride=shape["stride"], kind=kind,
              dtype_bytes=1 if kind == "q8" else 4)
    q_of = lambda w: (w + 2 * shape["padding"] - shape["s"]) \
        // shape["stride"] + 1
    ws = conv_working_set(h=shape["h"], w=shape["w"], q=q_of(shape["w"]),
                          **kw)
    ws_tall = conv_working_set(h=224, w=shape["w"], q=q_of(shape["w"]),
                               **kw)
    assert ws == ws_tall
    # while the resident-plane model must grow with the image
    wp = conv_working_set(h=shape["h"], w=shape["w"], q=q_of(shape["w"]),
                          whole_plane=True, **kw)
    wp_big = conv_working_set(h=224, w=224, q=q_of(224), whole_plane=True,
                              **kw)
    assert wp_big > wp


@settings(max_examples=50)
@given(st.floats(1e6, 1e15), st.floats(1.0, 1e12),
       st.floats(0.05, 1.0), st.integers(0, 100000))
def test_kernel_roofline_efficiency_in_unit_interval(flops, hbm, util,
                                                     n_steps):
    roof = kernel_roofline(flops=flops, hbm_bytes=hbm, util=util,
                           n_steps=n_steps)
    assert 0.0 < roof["efficiency"] <= 1.0
    assert roof["cost_s"] >= roof["step_time_s"] > 0.0
    assert roof["dominant"] in ("compute", "memory")


@settings(max_examples=25)
@given(st.lists(st.tuples(st.floats(1e6, 1e12), st.floats(1.0, 1e9),
                          st.floats(0.05, 1.0), st.integers(0, 1000)),
                min_size=1, max_size=4),
       st.floats(0.0, 1e9))
def test_composite_roofline_efficiency_and_conservation(parts, extra):
    dicts = [{"flops": f, "hbm_bytes": b, "util": u, "n_steps": n}
             for f, b, u, n in parts]
    roof = composite_roofline(dicts, extra_hbm_bytes=extra)
    assert 0.0 < roof["efficiency"] <= 1.0
    assert roof["launches"] == len(dicts)
    assert abs(roof["flops"] - sum(d["flops"] for d in dicts)) < 1e-6
    assert roof["hbm_bytes"] >= extra
    # serialized launches: composite cost >= any single launch's cost
    solo = kernel_roofline(**{k: dicts[0][k] for k in
                              ("flops", "hbm_bytes", "util", "n_steps")})
    assert roof["cost_s"] >= solo["cost_s"] - 1e-12


def test_stable_key_contracts():
    """The perfci extractors join on these names; renaming any of them is a
    baseline-schema change (bump perfci.SCHEMA_VERSION)."""
    shape = _shape(14, 64, 64, (3, 1), 1)
    t = conv_traffic(shape, _blk(shape))
    assert set(CONV_TRAFFIC_KEYS) <= set(t)
    roof = kernel_roofline(flops=1e9, hbm_bytes=1e6)
    assert tuple(roof) == KERNEL_ROOFLINE_KEYS
    comp = composite_roofline([t])
    assert tuple(comp) == COMPOSITE_ROOFLINE_KEYS


# -- depth-first chain pricing (DESIGN.md §16) -------------------------------

_chain_layers = st.lists(
    st.tuples(st.sampled_from([1, 3]),          # r == s
              st.integers(1, 2),                # stride
              st.sampled_from([8, 16, 32])),    # k
    min_size=2, max_size=4)


def _chain_shapes(h0, layers):
    shapes, h, c = [], h0, 8
    for r, stride, k in layers:
        pad = r // 2
        shapes.append({"h": h, "w": h, "c": c, "k": k, "r": r, "s": r,
                       "stride": stride, "padding": pad})
        h = (h + 2 * pad - r) // stride + 1
        c = k
    return shapes


@settings(max_examples=30)
@given(st.integers(16, 40), _chain_layers,
       st.sampled_from([1 << 18, 1 << 20, None]))
def test_chain_fused_never_exceeds_unfused(h0, layers, budget):
    """The fallback rule makes "fused <= unfused HBM" true on *every*
    generated chain and budget — exactly equal when the chain falls back,
    with zero intermediate bytes whenever it fuses."""
    t = chain_traffic(_chain_shapes(h0, layers), vmem_budget=budget)
    assert t["hbm_bytes"] <= t["unfused_hbm_bytes"] + 1e-6
    assert t["n_layers"] == len(layers)
    if t["fused"]:
        assert t["fits_vmem"]
        assert t["intermediate_bytes"] == 0.0
        if all(stride == 1 for _, stride, _k in layers):
            # stride-1 chains: bands cover every intermediate row, so halo
            # recompute can only add FLOPs (a strided consumer may instead
            # *skip* trailing producer rows the unfused path computes)
            assert t["flops"] >= sum(p["flops"]
                                     for p in t["unfused_parts"]) - 1e-6
    else:
        assert t["hbm_bytes"] == t["unfused_hbm_bytes"]
        assert t["intermediate_bytes"] == t["unfused_intermediate_bytes"]
        assert t["intermediate_bytes"] > 0.0


@settings(max_examples=30)
@given(st.integers(16, 40), _chain_layers,
       st.sampled_from([1 << 18, 1 << 20, None]))
def test_chain_roofline_consistent_with_traffic(h0, layers, budget):
    t = chain_traffic(_chain_shapes(h0, layers), vmem_budget=budget)
    roof = chain_roofline(t)
    assert tuple(roof) == CHAIN_ROOFLINE_KEYS
    assert roof["hbm_bytes"] == t["hbm_bytes"]
    assert roof["fused"] == t["fused"]
    assert 0.0 < roof["efficiency"] <= 1.0
    if not t["fused"]:
        # fallback prices the identical launch list: speedup exactly 1
        assert roof["speedup"] == 1.0
        assert roof["cost_s"] == roof["unfused_cost_s"]


def test_chain_stable_key_contracts():
    """perfci joins on these names too (SCHEMA_VERSION bump on rename)."""
    shapes = _chain_shapes(28, [(1, 1, 16), (3, 2, 16), (1, 1, 32)])
    t = chain_traffic(shapes)
    assert set(CHAIN_TRAFFIC_KEYS) <= set(t)
    assert tuple(chain_roofline(t)) == CHAIN_ROOFLINE_KEYS
