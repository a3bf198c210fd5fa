"""Message + serialization layer tests."""

import numpy as np
import pytest

from orleans_tpu.core import (
    ArraySchema,
    Category,
    Direction,
    GrainId,
    GrainType,
    Immutable,
    Message,
    RejectionType,
    ResponseKind,
    deep_copy,
    deserialize,
    make_request,
    make_rejection,
    make_response,
    serialize,
)


def _req(**kw):
    g = GrainId.for_grain(GrainType.of("Echo"), 1)
    defaults = dict(target_grain=g, interface_name="IEcho",
                    method_name="echo", body=("hi",))
    defaults.update(kw)
    return make_request(**defaults)


def test_request_defaults():
    m = _req()
    assert m.direction == Direction.REQUEST
    assert m.category == Category.APPLICATION
    assert m.response_kind == ResponseKind.SUCCESS
    assert m.expires_at is not None
    assert not m.is_expired


def test_correlation_ids_unique():
    a, b = _req(), _req()
    assert a.id != b.id


def test_response_swaps_endpoints():
    m = _req()
    m.target_activation = None
    r = make_response(m, "result")
    assert r.direction == Direction.RESPONSE
    assert r.id == m.id
    assert r.target_grain == m.sending_grain
    assert r.sending_grain == m.target_grain
    assert r.body == "result"


def test_rejection():
    m = _req()
    r = make_rejection(m, RejectionType.OVERLOADED, "busy")
    assert r.response_kind == ResponseKind.REJECTION
    assert r.rejection_type == RejectionType.OVERLOADED
    assert r.rejection_info == "busy"


def test_expiry():
    m = _req(timeout=0.0)
    import time
    time.sleep(0.001)
    assert m.is_expired


def test_deep_copy_isolation():
    payload = {"a": [1, 2, 3]}
    c = deep_copy(payload)
    c["a"].append(4)
    assert payload["a"] == [1, 2, 3]


def test_deep_copy_immutable_passthrough():
    payload = [1, 2]
    assert deep_copy(Immutable(payload)) is payload


def test_deep_copy_arrays_passthrough():
    a = np.arange(4)
    assert deep_copy(a) is a


def test_wire_roundtrip():
    m = _req()
    m2 = deserialize(serialize({"x": 1, "body": m.body}))
    assert m2["x"] == 1 and m2["body"] == ("hi",)


def test_array_schema_stack_unstack():
    sch = ArraySchema.of(x=(np.float32, (2,)), n=(np.int32, ()))
    payloads = [{"x": [i, i + 1], "n": i} for i in range(3)]
    batch = sch.stack(payloads, pad_to=8)
    assert batch["x"].shape == (8, 2)
    assert batch["n"].shape == (8,)
    assert batch["n"][2] == 2 and batch["n"][5] == 0
    rows = sch.unstack(batch, 3)
    assert len(rows) == 3
    assert rows[1]["n"] == 1


def test_array_schema_validate():
    sch = ArraySchema.of(x=(np.float32, (2,)))
    sch.validate({"x": np.zeros(2, np.float32)})
    with pytest.raises(ValueError):
        sch.validate({"x": np.zeros(3, np.float32)})


def test_error_response_exported_and_works():
    from orleans_tpu.core import make_error_response
    m = _req()
    r = make_error_response(m, ValueError("boom"))
    assert r.response_kind == ResponseKind.ERROR
    assert isinstance(r.body, ValueError)


def test_deep_copy_preserves_namedtuple_and_subclasses():
    import collections
    P = collections.namedtuple("P", "x y")
    assert deep_copy(P(1, 2)).x == 1
    assert type(deep_copy(P(1, 2))) is P
    d = collections.OrderedDict(a=1)
    assert type(deep_copy(d)) is collections.OrderedDict


def test_restricted_unpickler_blocks_unknown_modules():
    import pickle as _p
    evil = b"cposix\nsystem\n(S'true'\ntR."
    with pytest.raises(_p.UnpicklingError):
        deserialize(evil)
    # allowlisted types still round-trip
    import uuid as _uuid
    u = _uuid.uuid5(_uuid.NAMESPACE_DNS, "x")
    assert deserialize(serialize(u)) == u


def test_restricted_unpickler_blocks_builtins_eval():
    evil = b"cbuiltins\neval\n(S'1+1'\ntR."
    import pickle as _p
    with pytest.raises(_p.UnpicklingError):
        deserialize(evil)
    # safe builtins still work (exceptions cross the wire in error responses)
    assert isinstance(deserialize(serialize(ValueError("x"))), ValueError)


def test_stack_overflow_guard():
    sch = ArraySchema.of(x=(np.float32, ()))
    with pytest.raises(ValueError, match="exceeds pad_to"):
        sch.stack([{"x": 0.0}] * 10, pad_to=8)


# ---------------------------------------------------------------------------
# the receive pump's chunked framing (socket_fabric._read_frame_batches)
# ---------------------------------------------------------------------------

class _ChunkReader:
    """StreamReader stand-in feeding preset chunks."""

    def __init__(self, chunks):
        self.chunks = list(chunks)

    async def read(self, n):
        return self.chunks.pop(0) if self.chunks else b""


def _pump_messages(n: int = 5) -> list:
    from orleans_tpu.core.ids import GrainId, GrainType
    from orleans_tpu.core.message import make_request
    return [make_request(
        target_grain=GrainId.for_grain(GrainType.of("PumpGrain"), i),
        interface_name="PumpGrain", method_name=f"m{i}",
        body=((b"body-" * i,), {})) for i in range(n)]


def _collect_batches(chunks, strict_tail: bool = True) -> list:
    import asyncio
    from orleans_tpu.runtime.socket_fabric import _read_frame_batches

    async def collect():
        out = []
        async for msgs, bounces in _read_frame_batches(
                _ChunkReader(chunks), strict_tail=strict_tail):
            assert not bounces
            out.append([(m.method_name, m.body) for m in msgs])
        return out

    return asyncio.run(collect())


def test_pump_parses_frames_across_chunk_boundaries():
    from orleans_tpu.runtime.wire import encode_message
    msgs = _pump_messages()
    want = [(m.method_name, m.body) for m in msgs]
    blob = b"".join(encode_message(m) for m in msgs)
    # all at once, byte-by-byte, and ragged 7-byte chunks
    for chunking in ([blob],
                     [blob[i:i + 1] for i in range(len(blob))],
                     [blob[i:i + 7] for i in range(0, len(blob), 7)]):
        reads = _collect_batches(chunking)
        assert [x for read in reads for x in read] == want
        assert all(reads), "a read without a whole frame yields nothing"
    assert len(_collect_batches([blob])) == 1   # one read, one hand-off


def test_pump_mid_frame_eof_raises_on_a_silo_link_only():
    import asyncio
    import pytest
    from orleans_tpu.runtime.wire import encode_message
    first, torn = (encode_message(m) for m in _pump_messages(2))
    with pytest.raises(asyncio.IncompleteReadError):
        _collect_batches([first + torn[:-1]])
    # a gateway link: a torn tail is a clean close after what was whole
    assert [len(r) for r in _collect_batches([first + torn[:-1]],
                                             strict_tail=False)] == [1]


def test_pump_oversized_announcement_raises_after_the_frames_ahead():
    import asyncio
    import struct
    import pytest
    from orleans_tpu.runtime.socket_fabric import _read_frame_batches
    from orleans_tpu.runtime.wire import (MAX_FRAME_SEGMENT, FrameError,
                                          encode_message)
    good = encode_message(_pump_messages(1)[0])
    bad = struct.pack("<II", MAX_FRAME_SEGMENT + 1, 0) + b"x" * 16
    got = []

    async def pump():
        async for msgs, _b in _read_frame_batches(
                _ChunkReader([good + bad]), strict_tail=True):
            got.extend(m.method_name for m in msgs)

    with pytest.raises(FrameError):
        asyncio.run(pump())
    assert got == ["m0"]    # delivered, then the link drops
