"""fmda_tpu.stream.codec — the binary zero-copy data plane (ISSUE 12).

Round-trip soundness of the tagged binary format and its JSON fallback:
_minihyp/hypothesis-driven fuzz over the wire value model (NaN/±inf/
-0.0 floats, nested containers, unicode), array dtype/bit preservation,
columnar tick-block and packed-row layouts, truncated-buffer rejection
(every strict prefix of a valid frame must raise, never mis-parse), and
the wire_copy semantics the in-process buses lean on.  No jax, no
sockets — this is the codec alone; the transport is test_fleet_wire.
"""

import json
import math
import struct

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # hermetic image: no hypothesis wheel
    from _minihyp import given, settings, strategies as st

from fmda_tpu.stream import codec

SETTINGS = dict(max_examples=40, deadline=None)


def _round_trip(value, binary):
    payload = codec.encode_payload(value, binary=binary)
    out, was_binary = codec.decode_payload(payload)
    assert was_binary == binary
    return out


def _eq(a, b, nan_payload=True):
    """Structural equality with NaN == NaN and exact float identity
    (bit-for-bit: -0.0 != 0.0 matters on a bit-exact wire).
    ``nan_payload=False`` lets any NaN equal any NaN: JSON spells every
    NaN ``NaN``, so the fallback cannot carry a payload's bits (the
    fuzzer draws 0x7ff8000000000001 in about every other run)."""
    if isinstance(a, float) and isinstance(b, float):
        if not nan_payload and math.isnan(a) and math.isnan(b):
            return True
        return struct.pack("<d", a) == struct.pack("<d", b)
    if isinstance(a, dict) and isinstance(b, dict):
        return (a.keys() == b.keys()
                and all(_eq(v, b[k], nan_payload) for k, v in a.items()))
    if isinstance(a, list) and isinstance(b, list):
        return len(a) == len(b) and all(
            _eq(x, y, nan_payload) for x, y in zip(a, b))
    return type(a) is type(b) and a == b


# --------------------------------------------------------------- fuzzing

_SCALARS = st.one_of(
    st.none(),
    st.booleans(),
    st.integers(min_value=-(2 ** 62), max_value=2 ** 62),
    st.floats(),  # unbounded: NaN and ±inf included
    st.just(-0.0),
    st.just(math.nan),
    st.text(),
)

_VALUES = st.recursive(
    _SCALARS,
    lambda children: st.one_of(
        st.lists(children, max_size=6),
        st.dictionaries(st.text(max_size=8), children, max_size=6),
    ),
)


@given(value=_VALUES)
@settings(**SETTINGS)
def test_binary_round_trip_is_identity(value):
    assert _eq(_round_trip(value, binary=True), value)


@given(value=_VALUES)
@settings(**SETTINGS)
def test_json_fallback_round_trip_is_identity(value):
    assert _eq(_round_trip(value, binary=False), value, nan_payload=False)


@given(value=_VALUES)
@settings(**SETTINGS)
def test_truncated_buffer_always_rejected_never_misparsed(value):
    payload = codec.encode(value)
    # every strict prefix must raise CodecError — a truncated frame
    # that decodes to SOMETHING would be silent corruption.  (Sampled
    # stride keeps the fuzz pass fast on long frames.)
    step = max(1, len(payload) // 24)
    for cut in list(range(0, len(payload), step)) + [len(payload) - 1]:
        with pytest.raises(codec.CodecError):
            codec.decode(payload[:cut])


def test_trailing_garbage_rejected():
    payload = codec.encode({"a": 1})
    with pytest.raises(codec.CodecError, match="trailing"):
        codec.decode(payload + b"\x00")


def test_bad_magic_version_and_tag_rejected():
    with pytest.raises(codec.CodecError, match="magic"):
        codec.decode(b"\x00\x01\x00\x00")
    good = bytearray(codec.encode(None))
    good[1] = 99  # version
    with pytest.raises(codec.CodecError, match="version"):
        codec.decode(bytes(good))
    good = bytearray(codec.encode(None))
    good[4] = 0xEE  # value tag
    with pytest.raises(codec.CodecError, match="tag"):
        codec.decode(bytes(good))


# ----------------------------------------------------------------- arrays


@pytest.mark.parametrize("dtype", [
    np.float32, np.float64, np.int32, np.int64, np.uint8, np.bool_,
])
@pytest.mark.parametrize("binary", [True, False])
def test_array_dtype_and_bits_preserved(dtype, binary):
    rng = np.random.default_rng(0)
    a = (rng.standard_normal((3, 5)) * 100).astype(dtype)
    out = _round_trip({"a": a}, binary)["a"]
    assert out.dtype == a.dtype and out.shape == a.shape
    assert out.tobytes() == a.tobytes()  # bit identity, not just values


@pytest.mark.parametrize("binary", [True, False])
def test_array_specials_bit_exact(binary):
    a = np.array([np.nan, np.inf, -np.inf, -0.0, 0.0,
                  np.finfo(np.float32).tiny], np.float32)
    out = _round_trip(a, binary)
    assert out.tobytes() == a.tobytes()


@pytest.mark.parametrize("binary", [True, False])
def test_empty_and_zero_width_arrays(binary):
    for a in (np.zeros((0,), np.float32), np.zeros((0, 108), np.float32),
              np.zeros((4, 0), np.int64)):
        out = _round_trip(a, binary)
        assert out.shape == a.shape and out.dtype == a.dtype


def test_decoded_binary_array_is_zero_copy_readonly_view():
    a = np.arange(12, dtype=np.float32).reshape(3, 4)
    out = codec.decode(codec.encode(a))
    assert not out.flags.writeable  # immutable by construction
    with pytest.raises((ValueError, RuntimeError)):
        out[0, 0] = 1.0
    assert np.array_equal(out, a)


def test_object_dtype_rejected_everywhere():
    a = np.array([object()], dtype=object)
    with pytest.raises(codec.CodecError):
        codec.encode(a)
    with pytest.raises(codec.CodecError):
        codec.dumps(a)
    with pytest.raises(codec.CodecError):
        codec.wire_copy(a)


# ------------------------------------------------------------ tick blocks


def _tick_msgs(n, feats=6, pool=4, trace_every=0):
    rng = np.random.default_rng(1)
    msgs = []
    for i in range(n):
        m = {"kind": "tick", "session": f"S{i % pool}",
             "row": rng.standard_normal(feats).astype(np.float32),
             "seq": 100 + i}
        if trace_every and i % trace_every == 0:
            m["trace"] = f"t{i}:s{i}"
        msgs.append(m)
    return msgs


@pytest.mark.parametrize("binary", [True, False])
@pytest.mark.parametrize("n", [2, 256])
def test_tick_block_round_trip_both_formats(binary, n):
    msgs = _tick_msgs(n, trace_every=3)
    block = _round_trip(codec.pack_ticks(msgs), binary)
    back = list(codec.iter_ticks(block))
    assert [t[0] for t in back] == [m["session"] for m in msgs]
    assert [t[2] for t in back] == [m["seq"] for m in msgs]
    assert [t[3] for t in back] == [m.get("trace") for m in msgs]
    for t, m in zip(back, msgs):
        assert t[1].dtype == np.float32
        assert np.array_equal(t[1], m["row"])


def test_tick_block_rows_decode_into_one_contiguous_array():
    msgs = _tick_msgs(64, feats=108)
    block = codec.decode(codec.encode(codec.pack_ticks(msgs)))
    rows = block["rows"]
    assert rows.shape == (64, 108) and rows.dtype == np.float32
    assert rows.flags.c_contiguous  # staging copies straight out of it
    # each iterated row is a view into that one buffer, not a copy
    first = next(iter(codec.iter_ticks(block)))[1]
    assert first.base is not None


def test_coalesce_preserves_order_with_interleaved_control():
    ticks = _tick_msgs(6)
    msgs = (ticks[:3]
            + [{"kind": "open", "session": "S9"}]
            + ticks[3:5]
            + [{"kind": "close", "session": "S9"}]
            + ticks[5:])  # single trailing tick: below MIN_BLOCK_TICKS
    out = codec.coalesce_ticks(msgs)
    kinds = [m["kind"] for m in out]
    assert kinds == ["tick_block", "open", "tick_block", "close", "tick"]
    # unpacking in order reproduces the original tick sequence exactly
    seqs = []
    for m in out:
        if m["kind"] == "tick_block":
            seqs.extend(t[2] for t in codec.iter_ticks(m))
        elif m["kind"] == "tick":
            seqs.append(m["seq"])
    assert seqs == [t["seq"] for t in ticks]
    assert codec.coalesce_ticks([]) == []


def test_publish_many_frame_of_coalesced_ticks_is_the_same_rows_in_both_formats():
    """What a fleet tick pays: one ``publish_many`` frame whose ticks
    were coalesced into blocks.  The binary frame and the JSON fallback
    hand back the identical (B, F) float32 rows, bit for bit, and the
    rows that went in."""
    msgs = _tick_msgs(256, feats=108, pool=64)
    rows = np.stack([m["row"] for m in msgs])
    frame = {"op": "publish_many", "topic": "t",
             "values": codec.coalesce_ticks(msgs)}
    got = {}
    for binary in (True, False):
        out = _round_trip(frame, binary)
        assert out["op"] == "publish_many" and out["topic"] == "t"
        got[binary] = np.vstack(
            [np.asarray(b["rows"], np.float32) for b in out["values"]])
    assert got[True].tobytes() == got[False].tobytes() == rows.tobytes()


# ------------------------------------------------------------ packed rows


def test_pack_rows_round_trip_with_mixed_and_missing_keys():
    rows = [
        {"Timestamp": "2020-02-07 09:30:00", "Close": 1.5, "Vol": 2.0},
        {"Timestamp": "2020-02-07 09:31:00", "Close": -0.0, "Vol": 3.25,
         "Extra": "x"},
        {"Timestamp": "2020-02-07 09:32:00", "Close": math.inf, "Vol": 1e-300},
    ]
    back = codec.unpack_rows(
        codec.decode(codec.encode(codec.pack_rows(rows))))
    assert len(back) == len(rows)
    for a, b in zip(back, rows):
        assert a.keys() == b.keys()
        for k, v in b.items():
            if isinstance(v, float):
                assert struct.pack("<d", a[k]) == struct.pack("<d", v)
            else:
                assert a[k] == v


def test_pack_rows_empty():
    assert codec.unpack_rows(
        codec.decode(codec.encode(codec.pack_rows([])))) == []


# -------------------------------------------------------------- wire_copy


def test_wire_copy_decouples_containers_but_not_arrays():
    a = np.arange(4, dtype=np.float32)
    src = {"x": [1, 2], "a": a, "t": (1, 2)}
    out = codec.wire_copy(src)
    src["x"].append(3)
    assert out["x"] == [1, 2]          # container mutation decoupled
    assert out["t"] == [1, 2]          # tuples lower to lists (json parity)
    assert out["a"] is a               # arrays pass through uncopied


def test_wire_copy_coerces_keys_and_np_scalars_and_rejects_junk():
    out = codec.wire_copy({1: np.float64(2.5)})
    assert out == {"1": 2.5} and type(out["1"]) is float
    assert codec.wire_copy({True: "x", None: "y"}) == {
        "true": "x", "null": "y"}  # json.dumps key-coercion parity
    with pytest.raises(codec.CodecError):
        codec.wire_copy({"bad": object()})


# ------------------------------------------------------------- json layer


def test_json_fallback_is_plain_json_with_tagged_arrays():
    a = np.arange(3, dtype=np.int64)
    payload = codec.dumps({"a": a, "n": 1})
    doc = json.loads(payload)  # valid JSON text end to end
    assert doc["a"]["__nd__"][0] == a.dtype.str
    back = codec.loads(payload)
    assert np.array_equal(back["a"], a) and back["a"].dtype == a.dtype


def test_payload_auto_detection():
    v = {"x": 1}
    bin_payload = codec.encode_payload(v, binary=True)
    json_payload = codec.encode_payload(v, binary=False)
    assert codec.is_binary(bin_payload)
    assert not codec.is_binary(json_payload)
    assert codec.decode_payload(bin_payload) == (v, True)
    assert codec.decode_payload(json_payload) == (v, False)
    with pytest.raises(codec.CodecError):
        codec.loads(b"not json at all")


def test_int_beyond_i64_rejected_binary():
    with pytest.raises(codec.CodecError, match="i64"):
        codec.encode(2 ** 70)


def test_malformed_utf8_dict_key_is_codec_error_not_unicode_error():
    # dict KEYS decode outside the string-value try — the backstop in
    # decode() must still convert to CodecError, or one hostile frame
    # would kill a bus connection instead of costing one counted message
    good = codec.encode({"ab": 1})
    patched = good.replace(b"ab", b"\xff\xfe")
    with pytest.raises(codec.CodecError):
        codec.decode(patched)
