"""Quantized & compressed collectives: codec property sweep with
closed-form error bounds, negotiation verdicts, the mesh-mode one-XLA-
program path, tcp on-wire compression, the quantreport CLI, and the
procmode proofs (quantized path + negotiation fallback + compression
under chaos)."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from ompi_tpu.quant import codec as qcodec  # noqa: E402
from ompi_tpu.quant import negotiate as qneg  # noqa: E402
from ompi_tpu.quant.codec import chunk_layout, make_codec  # noqa: E402
from tests.test_process_mode import subprocess_env  # noqa: E402


def run_mpi(np_, script, *args, timeout=180, mca=(), env_extra=()):
    cmd = [sys.executable, "-m", "ompi_tpu.tools.mpirun", "-np", str(np_)]
    for k, v in mca:
        cmd += ["--mca", k, str(v)]
    cmd += [script, *args]
    env = subprocess_env()
    env.update(dict(env_extra))
    return subprocess.run(cmd, cwd=REPO, capture_output=True, text=True,
                          timeout=timeout, env=env)


# ------------------------------------------------------------------ codec
CONFIGS = [("int8", 8, 16), ("int8", 8, 64), ("int8", 8, 100),
           ("int8", 4, 64), ("fp8", 8, 64)]


@pytest.mark.parametrize("mode,bits,block", CONFIGS)
def test_roundtrip_bound_and_determinism(mode, bits, block):
    c = make_codec(mode, bits, block)
    rng = np.random.RandomState(0)
    for n in (1, 7, block, 3 * block + 5, 2000):
        x = (rng.randn(n) * rng.uniform(0.01, 100)).astype(np.float32)
        enc = c.encode(x)
        assert enc.size == c.wire_nbytes(n)
        assert np.array_equal(enc, c.encode(x))  # deterministic
        dec = c.decode(enc, n, np.float32)
        bound = c.error_bound(x)
        assert np.all(np.abs(dec - x) <= bound)


@pytest.mark.parametrize("mode,bits,block", CONFIGS)
@pytest.mark.parametrize("dtype", [np.float16, np.float32, np.float64])
@pytest.mark.parametrize("world", [1, 2, 3, 5])
def test_allreduce_bound_property_sweep(mode, bits, block, dtype, world):
    """|allreduce_quant(x) - allreduce_fp32(x)| <= codec.error_bound
    across dtypes, block sizes, and world sizes (the oracle is bitwise
    the procmode wire schedule — proven in check_quant.py)."""
    c = make_codec(mode, bits, block)
    rng = np.random.RandomState(world * 31 + block)
    xs = (rng.randn(world, 700)
          * rng.uniform(0.01, 30.0, (world, 1))).astype(dtype)
    res = c.simulate_allreduce(xs)
    assert res.dtype == np.dtype(dtype)
    exact = xs.astype(np.float64).sum(axis=0)
    bound = c.error_bound(xs, out_dtype=dtype)
    err = np.abs(res.astype(np.float64) - exact)
    assert np.all(err <= bound), float(np.max(err - bound))
    # bitwise determinism of the full pipeline
    assert np.array_equal(res, c.simulate_allreduce(xs))


@pytest.mark.parametrize("mode,bits", [("int8", 8), ("int8", 4),
                                       ("fp8", 8)])
def test_adversarial_inputs(mode, bits):
    """Denormals, +-inf amax blocks, all-zero blocks, and nan: the
    sentinel encoding carries non-finite values in place and the bound
    goes infinite exactly there."""
    c = make_codec(mode, bits, 32)
    x = np.zeros(320, np.float32)          # all-zero blocks
    x[3] = 1e-40                           # denormal
    x[40] = np.inf                         # +inf amax block
    x[75] = -np.inf
    x[76] = np.nan                         # nan amax block (with -inf)
    x[200:232] = 1e30                      # near-overflow amax
    enc = c.encode(x)
    dec = c.decode(enc, 320)
    assert dec[40] == np.inf
    assert dec[75] == -np.inf
    assert np.isnan(dec[76])
    bound = c.error_bound(x)
    fin = np.isfinite(bound)
    assert not fin[40] and not fin[75]
    assert np.all(np.abs(dec[fin] - x[fin]) <= bound[fin])
    # all-zero blocks decode to exact zeros
    assert np.all(dec[100:132] == 0.0)
    # world-stacked adversarial sweep
    xs = np.stack([x, -x, np.roll(x, 32)])
    res = c.simulate_allreduce(xs)
    b2 = c.error_bound(xs)
    with np.errstate(invalid="ignore"):
        err = np.abs(res.astype(np.float64)
                     - xs.astype(np.float64).sum(axis=0))
    ok = np.isfinite(b2)
    assert np.all(err[ok] <= b2[ok])


def test_f64_amax_beyond_f32_scale_range_saturates():
    """A float64 block whose amax exceeds f32max * divisor can't ship
    its scale in the f32 wire slot. The encode must NOT overflow the
    scale to inf (decode would misread the non-finite sentinel and
    silently zero the block): it clamps to f32max, values saturate,
    and error_bound honestly reports inf for that block."""
    from ompi_tpu.quant.codec import make_codec

    codec = make_codec("int8", 8, 64)
    x = np.full(64, 1e50, np.float64)
    dec = codec.decode(codec.encode(x), 64, np.float64)
    assert np.all(np.isfinite(dec))
    assert np.all(dec > 1e40), dec[:2]  # saturated, NOT zeroed
    assert np.all(np.isinf(codec.error_bound(x)))
    # and through the full allreduce schedule + its 2-D bound
    xs = np.stack([x, x * 0.5])
    res = codec.simulate_allreduce(xs)
    assert np.all(np.isfinite(res)) and np.all(res > 1e40)
    assert np.all(np.isinf(codec.error_bound(xs)))
    # a representable f64 amax keeps its finite bound and round-trips
    ok = np.full(64, 1e30, np.float64)
    b = codec.error_bound(ok)
    assert np.all(np.isfinite(b))
    assert np.all(np.abs(codec.decode(codec.encode(ok), 64, np.float64)
                         - ok) <= b)


def test_wire_ratio_targets():
    c8 = make_codec("int8", 8, 64)
    c4 = make_codec("int8", 4, 64)
    assert c8.ratio(1 << 20) >= 3.5       # the acceptance floor
    assert c4.ratio(1 << 20) >= 7.0


def test_chunk_layout_invariants():
    for count in (1, 63, 64, 1000, 12345):
        for world in (1, 2, 3, 8):
            per, padded = chunk_layout(count, world, 64)
            assert per % 64 == 0
            assert padded == per * world
            assert padded >= count


def test_codec_rejects_bad_config():
    with pytest.raises(ValueError):
        make_codec("fp8", 4, 64)
    with pytest.raises(ValueError):
        make_codec("int3", 8, 64)
    with pytest.raises(ValueError):
        make_codec("int8", 8, 0)


# ------------------------------------------------------------- negotiate
GOOD = {"enable": 1, "bits": 8, "block": 64, "mode": "int8",
        "min_bytes": 4096, "strict": 0, "fp8_ok": 1}


def test_negotiate_verdicts():
    st = qneg.decide([dict(GOOD), dict(GOOD), dict(GOOD)])
    assert st.active and st.min_bytes == 4096
    assert st.codec.block == 64
    # one member off -> everyone falls back, not strict
    st = qneg.decide([dict(GOOD), dict(GOOD, enable=0)])
    assert not st.active and not st.strict and "unset" in st.reason
    # strict only arms when an ENABLED member asked for it
    st = qneg.decide([dict(GOOD, strict=1), dict(GOOD, enable=0)])
    assert not st.active and st.strict
    st = qneg.decide([dict(GOOD), dict(GOOD, enable=0, strict=1)])
    assert not st.active and not st.strict
    # mismatched config
    st = qneg.decide([dict(GOOD), dict(GOOD, block=32)])
    assert not st.active and "mismatched" in st.reason
    # inactive verdicts keep the enabled members' min_bytes floor: a
    # strict-armed state gates _check_armed through _eligible, and the
    # dataclass default (64 KiB) would silently no-op quant_strict for
    # payloads between the configured floor and 64 KiB
    st = qneg.decide([dict(GOOD, min_bytes=1024, strict=1),
                      dict(GOOD, min_bytes=1024, bits=4)])
    assert not st.active and st.strict and st.min_bytes == 1024
    st = qneg.decide([dict(GOOD, min_bytes=2048, strict=1),
                      dict(GOOD, enable=0)])
    assert not st.active and st.strict and st.min_bytes == 2048
    # symmetric threshold: max wins
    st = qneg.decide([dict(GOOD, min_bytes=1 << 20), dict(GOOD)])
    assert st.active and st.min_bytes == 1 << 20
    # fp8 with bits=4 is rejected at the verdict
    st = qneg.decide([dict(GOOD, mode="fp8", bits=4)] * 2)
    assert not st.active
    # fp8 availability is decided from the SHARED cards, not a local
    # ml_dtypes probe: one build without it flips EVERY rank to the
    # same fallback (a local probe would tear the collective)
    st = qneg.decide([dict(GOOD, mode="fp8"),
                      dict(GOOD, mode="fp8", fp8_ok=0)])
    assert not st.active and "unavailable" in st.reason
    st = qneg.decide([dict(GOOD, mode="fp8")] * 2)
    assert st.active and st.mode == "fp8"


def test_negotiate_card_roundtrip():
    card = json.loads(qneg.card_json())
    assert set(card) == {"enable", "bits", "block", "mode", "min_bytes",
                         "strict", "fp8_ok"}


# ------------------------------------------------- fallback delegation
def test_coll_table_records_full_priority_chain(monkeypatch):
    """Winning a slot must not orphan the losers: the table records the
    FULL priority-ordered chain per contested slot so conditional
    components (quant, hier) can route ineligible calls to whatever
    would otherwise own the slot — and a conditional runner-up can
    delegate onward from ITS position instead of re-entering itself."""
    from ompi_tpu.coll import base as cb

    class Hi(cb.CollModule):
        def allreduce(self, comm, *a):
            return "hi"

    class Mid(cb.CollModule):
        def allreduce(self, comm, *a):
            return "mid"

        def allgather(self, comm, *a):
            return "mid"

    class Lo(cb.CollModule):
        def allreduce(self, comm, *a):
            return "lo"

    monkeypatch.setattr(
        cb.coll_framework, "select_all",
        lambda comm=None: [(110, "hi", Hi()), (50, "mid", Mid()),
                           (30, "lo", Lo())])
    t = cb._select_coll(object())
    assert t.providers["allreduce"] == "hi"
    # the whole losing chain, in priority order
    assert t.fallback_providers["allreduce"] == ["mid", "lo"]
    assert [f(None) for f in t.fallbacks["allreduce"]] == ["mid", "lo"]
    # uncontested slots record no fallback
    assert t.providers["allgather"] == "mid"
    assert "allgather" not in t.fallbacks


def test_quant_delegate_prefers_fallback_slot():
    """QuantProcColl._delegate serves the comm's recorded runner-up
    (smcoll/han/hier/adaptive outrank tuned, so a hard-wired tuned
    would downgrade them); a missing runner-up is an invariant
    violation (coll/basic provides every op) and surfaces loudly."""
    from ompi_tpu.coll.base import CollTable
    from ompi_tpu.coll.quant import QuantProcColl

    def runner_up(comm, *a):
        return "next-best"

    class WithFallback:
        coll = CollTable()
        coll.providers["allreduce"] = "quant"
        coll.fallbacks["allreduce"] = [runner_up]
        coll.fallback_providers["allreduce"] = ["mid"]

    class WithoutFallback:
        coll = CollTable()

    m = QuantProcColl()
    assert m._delegate(WithFallback(), "allreduce") is runner_up
    with pytest.raises(KeyError):
        m._delegate(WithoutFallback(), "allreduce")


# ------------------------------------------------------------- mesh mode
@pytest.fixture
def quant_mesh():
    from ompi_tpu.mca.var import set_var

    set_var("quant", "enable", True)
    set_var("quant", "min_bytes", 1024)
    try:
        from ompi_tpu.parallel import mesh_world

        yield mesh_world(axis_name="quant_test_axis")
    finally:
        set_var("quant", "enable", False)
        set_var("quant", "min_bytes", 65536)


def test_mesh_quant_allreduce_bound_and_dispatch(quant_mesh):
    world = quant_mesh
    W = world.world_size
    assert world.coll.providers.get("allreduce") == "quant"
    rng = np.random.RandomState(0)
    xs = (rng.randn(W, 2048) * 4).astype(np.float32)
    x = world.shard(xs)
    res = np.asarray(world.allreduce(x))
    # every mesh row agrees (the allgather phase republishes one value)
    assert np.array_equal(res[0], res[W - 1])
    c = make_codec("int8", 8, 64)
    err = np.abs(res[0].astype(np.float64)
                 - xs.astype(np.float64).sum(axis=0))
    assert np.all(err <= c.error_bound(xs))
    # deterministic re-dispatch through the promoted fast table
    assert ("allreduce" in [k[0] for k in world._fast])
    assert np.array_equal(res, np.asarray(world.allreduce(x)))


def test_mesh_quant_delegates_ineligible(quant_mesh):
    world = quant_mesh
    W = world.world_size
    # ints and small floats ride the plain (exact) body of the SAME
    # compiled slot
    ints = np.arange(W * 4096, dtype=np.int32).reshape(W, 4096)
    r = np.asarray(world.allreduce(world.shard(ints)))
    assert np.array_equal(r[0], ints.sum(axis=0))
    small = np.full((W, 8), 1.5, np.float32)
    r2 = np.asarray(world.allreduce(world.shard(small)))
    np.testing.assert_allclose(r2[0], small.sum(axis=0), rtol=1e-6)


def test_mesh_reduce_allreduce_order_independent():
    """XlaColl.reduce shares the PLAIN allreduce executable on the same
    comm; the quant module caches under a discriminated key, so which
    body runs must NOT depend on reduce/allreduce call order: reduce
    stays exact, allreduce quantizes — both orders."""
    from ompi_tpu.mca.var import set_var
    from ompi_tpu.parallel import mesh_world

    set_var("quant", "enable", True)
    set_var("quant", "min_bytes", 1024)
    try:
        rng = np.random.RandomState(5)
        c = make_codec("int8", 8, 64)
        for order, axis in (("reduce_first", "qorder_a"),
                            ("allreduce_first", "qorder_b")):
            world = mesh_world(axis_name=axis)
            W = world.world_size
            xs = (rng.randn(W, 2048) * 4).astype(np.float32)
            x = world.shard(xs)
            exact = xs.astype(np.float64).sum(axis=0)
            if order == "reduce_first":
                red = np.asarray(world.reduce(x))[0]
                ar = np.asarray(world.allreduce(x))[0]
            else:
                ar = np.asarray(world.allreduce(x))[0]
                red = np.asarray(world.reduce(x))[0]
            # reduce is exact (never negotiated for quantization)
            np.testing.assert_allclose(red.astype(np.float64), exact,
                                       rtol=1e-5, atol=1e-3,
                                       err_msg=order)
            # allreduce is quantized: inside the bound but NOT exact
            err = np.abs(ar.astype(np.float64) - exact)
            assert np.all(err <= c.error_bound(xs)), order
            assert float(err.max()) > 1e-3, \
                f"{order}: allreduce ran full precision (key collision)"
            # the fast table serves the same bodies on re-dispatch
            assert np.array_equal(ar, np.asarray(world.allreduce(x))[0])
            np.testing.assert_allclose(
                np.asarray(world.reduce(x))[0].astype(np.float64),
                exact, rtol=1e-5, atol=1e-3,
                err_msg=order + " promoted")
    finally:
        set_var("quant", "enable", False)
        set_var("quant", "min_bytes", 65536)


def test_mesh_quant_adversarial_sentinels(quant_mesh):
    """The traced body carries non-finite blocks the codec way: ±inf
    and nan propagate IN PLACE (inf-scale sentinel + code points), the
    rest of the payload stays inside the bound — not a whole-block NaN
    wipeout."""
    world = quant_mesh
    W = world.world_size
    rng = np.random.RandomState(11)
    xs = (rng.randn(W, 2048) * 3).astype(np.float32)
    xs[0, 100] = np.inf
    xs[1, 300] = -np.inf
    xs[0, 500] = np.nan
    res = np.asarray(world.allreduce(world.shard(xs)))[0]
    assert res[100] == np.inf
    assert res[300] == -np.inf
    assert np.isnan(res[500])
    c = make_codec("int8", 8, 64)
    bound = c.error_bound(xs)
    fin = np.isfinite(bound)
    with np.errstate(invalid="ignore"):
        err = np.abs(res.astype(np.float64)
                     - xs.astype(np.float64).sum(axis=0))
    assert np.all(err[fin] <= bound[fin])


def test_negotiate_cache_and_invalidate():
    """Only a genuinely-absent card (TimeoutError) negotiates as
    disabled; other fetch errors propagate (a one-rank hiccup must
    fail loudly, not silently split the verdict). invalidate_cards
    drops the cache so post-recovery negotiation reads fresh."""

    class FakeModex:
        def __init__(self, err):
            self.err = err
            self.calls = 0

        def get(self, rank, key, timeout=None):
            self.calls += 1
            raise self.err

    qneg._reset_for_testing()
    try:
        m = FakeModex(TimeoutError("never appeared"))
        card = qneg._member_card(m, 7)
        assert card == {"enable": 0, "_missing": True}
        qneg._member_card(m, 7)
        assert m.calls == 1  # cached
        qneg.invalidate_cards()
        qneg._member_card(m, 7)
        assert m.calls == 2  # re-fetched after invalidation
        with pytest.raises(OSError):
            qneg._member_card(FakeModex(OSError("transport")), 8)
    finally:
        qneg._reset_for_testing()


def test_mesh_quant_counters_track_live(quant_mesh):
    """The mesh path feeds quant_colls/quant_bytes_* too (the promoted
    fast-table entry carries the accounting wrapper), and the counted
    ratio clears the >= 3.5x acceptance floor."""
    from ompi_tpu import quant
    from ompi_tpu.mca.var import all_pvars

    quant._reset_for_testing()
    world = quant_mesh
    W = world.world_size
    xs = np.ones((W, 4096), np.float32)
    x = world.shard(xs)
    world.allreduce(x)          # slow path + promote
    world.allreduce(x)          # fast-table path
    pv = all_pvars()
    assert pv["quant_colls"].value == 2
    wire = pv["quant_bytes_wire"].value
    saved = pv["quant_bytes_saved"].value
    assert wire > 0 and (saved + wire) / wire >= 3.5
    # ineligible (int) dispatch through the same slot is NOT counted
    world.allreduce(world.shard(np.ones((W, 4096), np.int32)))
    assert all_pvars()["quant_colls"].value == 2
    # bfloat16 IS floating on jnp's lattice (np.issubdtype disagrees):
    # it quantizes on the wire, so it must be counted too
    import jax.numpy as jnp

    world.allreduce(world.shard(jnp.ones((W, 4096), jnp.bfloat16)))
    assert all_pvars()["quant_colls"].value == 3
    quant._reset_for_testing()


def test_mesh_quant_under_outer_jit(quant_mesh):
    """Calling the quantized allreduce inside an outer jit/scan must
    (a) not bake outer-trace tracers into the cached executable — the
    first-ever dispatch happening under tracing used to poison the
    cache so the next EAGER call raised UnexpectedTracerError — and
    (b) leave the pvars untouched: the accounting wrapper runs once at
    trace time while the collective executes per call, so counting
    there would be wrong in both directions."""
    import jax
    import jax.numpy as jnp

    from ompi_tpu import quant
    from ompi_tpu.mca.var import all_pvars

    quant._reset_for_testing()
    world = quant_mesh
    W = world.world_size
    x = world.shard(np.ones((W, 4096), np.float32))

    @jax.jit
    def chain(b):
        def step(c, _):
            return world.allreduce(c) * (1.0 / W), None
        return jax.lax.scan(step, b, None, length=3)[0]

    r = np.asarray(chain(x))          # first dispatch happens TRACED
    np.testing.assert_allclose(r[0], np.ones(4096), atol=0.5)
    assert all_pvars()["quant_colls"].value == 0  # traced: unaccounted
    out = np.asarray(world.allreduce(x))  # eager reuse of the cache
    np.testing.assert_allclose(out[0], np.full(4096, float(W)), atol=0.5)
    assert all_pvars()["quant_colls"].value == 1
    quant._reset_for_testing()


def test_mesh_plain_world_untouched():
    """Without quant_enable the xla component owns allreduce — the
    default mesh path never routes through the quant module."""
    from ompi_tpu.parallel import mesh_world

    world = mesh_world(axis_name="plain_test_axis")
    assert world.coll.providers.get("allreduce") == "xla"


# ----------------------------------------------------- tcp compression
def _pump(btls, done, timeout=10.0):
    deadline = time.time() + timeout
    while not done() and time.time() < deadline:
        for b in btls:
            b.progress()
        time.sleep(0.002)
    assert done(), "tcp pump timed out"


def test_tcp_compress_roundtrip_and_negotiation():
    from ompi_tpu import quant
    from ompi_tpu.btl.tcp import TcpBtl
    from ompi_tpu.mca.var import all_pvars, set_var
    from ompi_tpu.pml.base import pack_header

    quant._reset_for_testing()
    set_var("btl_tcp", "compress", 6)
    set_var("btl_tcp", "compress_min_bytes", 1024)
    got = {"a": [], "b": []}
    # deliver hands BORROWED views of the rx pool block: a test
    # that stashes payloads must copy at its boundary, exactly
    # like the pml does
    a = TcpBtl(lambda h, p: got["a"].append(bytes(p)), my_rank=90)
    b = TcpBtl(lambda h, p: got["b"].append(bytes(p)), my_rank=91)
    a.set_peers({91: f"{b.host}:{b.port}"})
    b.set_peers({90: f"{a.host}:{a.port}"})
    try:
        hdr = pack_header(1, 0, 0, 7, 0, 0, 0, 0)
        compressible = bytes(np.zeros(150000, np.uint8))
        incompressible = np.random.RandomState(0).bytes(150000)
        small = b"x" * 64
        a.send(91, hdr, compressible)        # pre-ack: raw framing
        _pump([a, b], lambda: len(got["b"]) >= 1)
        a.send(91, hdr, compressible)        # post-ack: compressed
        a.send(91, hdr, incompressible)      # stays raw (no win)
        a.send(91, hdr, small)               # under the floor
        _pump([a, b], lambda: len(got["b"]) >= 4)
        assert got["b"] == [compressible, compressible,
                            incompressible, small]
        b.send(90, hdr, compressible)        # acceptor side compresses
        _pump([a, b], lambda: len(got["a"]) >= 1)
        assert got["a"] == [compressible]
        c = quant.counters()
        assert c["wire_frames"] == 2
        assert c["wire_comp"] < c["wire_raw"] // 50
        assert all_pvars()["btl_tcp_compress_ratio"].value > 1.0
        assert all_pvars()["btl_tcp_compress_saved_bytes"].value > 0
    finally:
        set_var("btl_tcp", "compress", 0)
        a.finalize()
        b.finalize()


def test_tcp_compress_direction_independent():
    """Engagement must not depend on which side dialed: the capability
    bit advertises DECODE support (unconditional in this build), so a
    compress-enabled rank flags frames to a compress=0 peer even when
    that peer connected first."""
    from ompi_tpu import quant
    from ompi_tpu.btl.tcp import TcpBtl
    from ompi_tpu.mca.var import set_var
    from ompi_tpu.pml.base import pack_header

    quant._reset_for_testing()
    set_var("btl_tcp", "compress", 0)       # the DIALER stays at 0
    set_var("btl_tcp", "compress_min_bytes", 1024)
    got = {"e": [], "f": []}
    e = TcpBtl(lambda h, p: got["e"].append(bytes(p)), my_rank=86)
    f = TcpBtl(lambda h, p: got["f"].append(bytes(p)), my_rank=87)
    e.set_peers({87: f"{f.host}:{f.port}"})
    f.set_peers({86: f"{e.host}:{e.port}"})
    hdr = pack_header(1, 0, 0, 7, 0, 0, 0, 0)
    payload = bytes(np.zeros(150000, np.uint8))
    try:
        f.send(86, hdr, b"hello")           # f dials e FIRST
        _pump([e, f], lambda: len(got["e"]) >= 1)
        set_var("btl_tcp", "compress", 6)   # e compresses over the
        e.send(87, hdr, payload)            # accepted (f-dialed) conn
        _pump([e, f], lambda: len(got["f"]) >= 1)
        assert got["f"] == [payload]
        assert quant.counters()["wire_frames"] == 1  # flagged frame moved
    finally:
        set_var("btl_tcp", "compress", 0)
        set_var("btl_tcp", "compress_min_bytes", 1 << 16)
        e.finalize()
        f.finalize()


def test_tcp_frame_size_guard():
    """Bit 31 of the length word is the compression flag, capping one
    frame at 2 GiB. An oversized frame must raise loudly at the sender
    — packed silently, the receiver would mask a wrong length and
    misparse the frame as compressed, killing a healthy link."""
    from ompi_tpu.btl.tcp import TcpBtl, _LEN_MASK
    from ompi_tpu.core.errors import MPIError
    from ompi_tpu.pml.base import pack_header

    class Huge(bytes):
        def __len__(self):
            return _LEN_MASK + 1

    b = TcpBtl(lambda h, p: None, my_rank=96)
    try:
        with pytest.raises(MPIError, match="framing limit"):
            b.send(97, pack_header(1, 0, 0, 7, 0, 0, 0, 0), Huge())
    finally:
        b.finalize()


def test_tcp_corrupt_compressed_frame_fails_link():
    """A zlib-flagged frame that won't decompress is a stream-integrity
    loss: the LINK dies (the PR 3 failover/dead-letter path engages)
    instead of silently dropping one frame — which would leave the
    pml's per-peer sequence waiting forever on the hole."""
    import socket as socklib
    import struct

    from ompi_tpu.btl.tcp import TcpBtl, _CAP_COMPRESS, _ZFLAG
    from ompi_tpu.mca.var import set_var
    from ompi_tpu.pml.base import HDR_SIZE, pack_header

    set_var("btl_tcp", "compress", 6)
    got = []
    b = TcpBtl(lambda h, p: got.append(bytes(p)), my_rank=95)
    s = None
    try:
        s = socklib.create_connection((b.host, b.port))
        s.sendall(struct.pack("<I", 94 | _CAP_COMPRESS))
        _pump([b], lambda: 94 in b.conns)
        assert s.recv(4)  # the acceptor's capability ack
        hdr = pack_header(1, 0, 0, 7, 0, 0, 0, 0)
        garbage = b"\x00not-zlib-data" * 16
        s.sendall(struct.pack(
            "<I", (HDR_SIZE + len(garbage)) | _ZFLAG) + hdr + garbage)
        _pump([b], lambda: b.conns[94].dead is not None)
        assert b.conns[94].dead is not None
        assert got == []  # the garbage never reached deliver
    finally:
        set_var("btl_tcp", "compress", 0)
        if s is not None:
            s.close()
        b.finalize()


def test_tcp_noncompressing_peer_interops():
    """With compression off on both sides nothing is ever flagged
    (the capability bit only advertises DECODE support); payloads
    arrive intact and the compression counters stay at zero."""
    from ompi_tpu import quant
    from ompi_tpu.btl.tcp import TcpBtl
    from ompi_tpu.mca.var import set_var
    from ompi_tpu.pml.base import pack_header

    quant._reset_for_testing()
    set_var("btl_tcp", "compress", 0)
    got = {"c": []}
    c = TcpBtl(lambda h, p: got["c"].append(p), my_rank=92)
    d = TcpBtl(lambda h, p: None, my_rank=93)
    d.set_peers({92: f"{c.host}:{c.port}"})
    try:
        payload = bytes(np.zeros(150000, np.uint8))
        d.send(92, pack_header(1, 0, 0, 7, 0, 0, 0, 0), payload)
        _pump([c, d], lambda: len(got["c"]) >= 1)
        assert got["c"] == [payload]
        assert quant.counters()["wire_frames"] == 0
    finally:
        c.finalize()
        d.finalize()


# ----------------------------------------------------------- quantreport
def test_quantreport_fast_subset(tmp_path):
    from ompi_tpu.mca.var import set_var

    set_var("metrics", "dir", str(tmp_path))
    try:
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import quantreport

        rc = quantreport.main(["--fast", "--world", "3"])
        assert rc == 0
        data = json.loads((tmp_path / "quant-report.json").read_text())
        assert all(r["bound_holds"] for r in data["configs"]
                   if "error" not in r)
        assert any(r["wire_ratio"] >= 3.5 for r in data["configs"]
                   if "error" not in r)
    finally:
        set_var("metrics", "dir", ".")


# ------------------------------------------------------------ observability
def test_note_coll_counters_and_pvars():
    from ompi_tpu import quant
    from ompi_tpu.mca.var import all_pvars

    quant._reset_for_testing()
    quant.note_coll("allreduce", 1000, 250)
    quant.note_coll("allgather", 400, 100)
    pv = all_pvars()
    assert pv["quant_colls"].value == 2
    assert pv["quant_bytes_wire"].value == 350
    assert pv["quant_bytes_saved"].value == 1050
    quant._reset_for_testing()


# -------------------------------------------------------------- procmode
def test_procmode_quantized_collectives():
    r = run_mpi(3, "tests/procmode/check_quant.py", "quant",
                env_extra=(("OMPI_TPU_MCA_quant_enable", "1"),
                           ("OMPI_TPU_MCA_quant_min_bytes", "2048")))
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("QUANT-OK") == 3


def test_procmode_negotiation_fallback():
    """One rank without quant_enable: every rank falls back together —
    exact results, zero quant collectives, clean exit (no torn hang)."""
    r = run_mpi(3, "tests/procmode/check_quant.py", "fallback")
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("FALLBACK-OK") == 3


def test_procmode_tcp_compression_under_chaos():
    """Compressed rendezvous payloads round-trip byte-identically over
    the tcp-only path with chaos delay + dup injection armed."""
    r = run_mpi(2, "tests/procmode/check_quant.py", "compress",
                mca=(("btl_btl", "^sm"),
                     ("btl_tcp_compress", "6"),
                     ("btl_tcp_compress_min_bytes", "4096"),
                     ("ft_inject_seed", "5"),
                     ("ft_inject_plan", "delay(0,1,ms=5);dup(0,1,nth=9)")))
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("COMPRESS-OK") == 2
