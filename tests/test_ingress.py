"""Network ingress tests: framing codec, session fault semantics, drain.

The ingress contract under test: every failure mode has exactly one
explicit observable — sheds arrive as `ERR_OVERLOADED` frames (and the
session survives), protocol errors arrive as typed ERR frames >= 0x100
(and the session dies), stalled peers are reaped by the read deadline,
and a graceful close flushes every submitted response first. The
retry client must classify these correctly: retry sheds and
disconnects, never protocol errors.
"""

import socket
import threading
import time

import pytest

from conftest import *  # noqa: F401,F403 (env setup)

from bitcoinconsensus_tpu.api import Error
from bitcoinconsensus_tpu.core.flags import VERIFY_ALL_LIBCONSENSUS
from bitcoinconsensus_tpu.core.script_error import ScriptError
from bitcoinconsensus_tpu.models.batch import (
    BatchItem,
    BatchResult,
    verify_batch,
)
from bitcoinconsensus_tpu.resilience.faults import (
    FaultPlan,
    FaultSpec,
    inject,
)
from bitcoinconsensus_tpu.serving import (
    IngressClient,
    IngressProtocolError,
    IngressServer,
    OverloadError,
    PendingVerify,
    VerifyServer,
    verify_with_retry,
)
from bitcoinconsensus_tpu.serving import ingress as ingress_mod
from bitcoinconsensus_tpu.serving.ingress import (
    ERR_PROTO_BAD_TYPE,
    ERR_PROTO_MALFORMED,
    ERR_PROTO_OVERSIZED,
    FRAME_ERR,
    FRAME_REQ,
    FRAME_RESP,
    HEADER_LEN,
    decode_error_payload,
    decode_header,
    decode_item,
    decode_request,
    decode_response_payload,
    encode_error,
    encode_frame,
    encode_item,
    encode_request,
    encode_response,
)

from test_batch import make_p2wpkh_spend

pytestmark = pytest.mark.usefixtures("warm_kernel")  # conftest.py: first calls


def _items(n=4, bad_first=True):
    out = []
    for i in range(n):
        txb, spk, amt = make_p2wpkh_spend(
            f"ingress-test-{i}", corrupt=(bad_first and i == 0)
        )
        out.append(BatchItem(txb, 0, VERIFY_ALL_LIBCONSENSUS,
                             spent_output_script=spk, amount=amt))
    return out


def _recv_exactly(sock, n):
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        assert chunk, "connection closed mid-frame"
        buf += chunk
    return buf


def _recv_frame(sock):
    ftype, ln = decode_header(_recv_exactly(sock, HEADER_LEN))
    return ftype, _recv_exactly(sock, ln)


class _StubVerify:
    """Stand-in for `VerifyServer`: settles each submit on a timer
    thread (`delay_s`) or sheds (`shed_reason`). Lets ingress tests run
    without device work."""

    def __init__(self, delay_s=0.0, shed_reason=None, ok=True):
        self.delay_s = delay_s
        self.shed_reason = shed_reason
        self.ok = ok
        self.submitted = []

    def submit(self, item, tenant="default"):
        if self.shed_reason is not None:
            raise OverloadError(self.shed_reason)
        req = PendingVerify(item, tenant, 0.0)
        self.submitted.append(req)
        res = (
            BatchResult.success()
            if self.ok
            else BatchResult(False, Error.ERR_SCRIPT, ScriptError.EVAL_FALSE)
        )
        if self.delay_s > 0:
            threading.Timer(self.delay_s, req._resolve, (res,)).start()
        else:
            req._resolve(res)
        return req


# -- wire codec --------------------------------------------------------


def test_item_codec_roundtrip_variants():
    variants = [
        BatchItem(b"\x01" * 60, 0, 0),
        BatchItem(b"tx", 3, 0x1F, spent_output_script=b"", amount=0),
        BatchItem(b"tx", 1, 2, spent_output_script=b"\x51", amount=-1),
        BatchItem(
            b"x" * 5, 2, VERIFY_ALL_LIBCONSENSUS,
            amount=21_000_000 * 100_000_000,
            spent_outputs=[(0, b""), (12345, b"\x00" * 40)],
        ),
    ]
    for item in variants:
        assert decode_item(encode_item(item)) == item


def test_request_codec_roundtrip():
    item = _items(1, bad_first=False)[0]
    rid, tenant, got = decode_request(
        encode_request(7, "tenant-é", item)
    )
    assert rid == 7 and tenant == "tenant-é" and got == item


def test_response_codec_roundtrip():
    for res in (
        BatchResult.success(),
        BatchResult(False, Error.ERR_SCRIPT, ScriptError.EVAL_FALSE),
        BatchResult(False, Error.ERR_TX_DESERIALIZE, None),
    ):
        rid, got = decode_response_payload(encode_response(9, res))
        assert rid == 9
        assert (got.ok, got.error, got.script_error) == (
            res.ok, res.error, res.script_error,
        )


def test_error_codec_roundtrip():
    rid, code, reason = decode_error_payload(
        encode_error(0, ERR_PROTO_OVERSIZED, "too big")
    )
    assert (rid, code, reason) == (0, ERR_PROTO_OVERSIZED, "too big")


def test_malformed_payload_rejected():
    item = _items(1, bad_first=False)[0]
    payload = encode_request(1, "t", item)
    with pytest.raises(ValueError):
        decode_request(payload[:-3])  # truncated
    with pytest.raises(ValueError):
        decode_request(payload + b"\x00")  # trailing garbage


# -- end-to-end over the socket ----------------------------------------


def _observations(name, label, value):
    """(count, sum) of a labelled histogram of the global registry."""
    from bitcoinconsensus_tpu.obs import get_registry

    for s in get_registry().get(name)._samples():
        if s["labels"] == {label: value}:
            return s["count"], s["sum"]
    return 0, 0.0


def _stage(stage):
    return _observations("consensus_ingress_seconds", "stage", stage)


def _span_count(name):
    return _observations("consensus_span_duration_seconds", "span", name)[0]


def test_socket_verify_bit_identical_to_direct():
    items = _items(4)
    direct = verify_batch(items)
    stages0 = {st: _stage(st) for st in ("decode", "respond")}
    spans0 = {n: _span_count(n) for n in ("serving.idle", "serving.take")}
    with VerifyServer() as vs:
        with IngressServer(vs, idle_s=10.0) as ing:
            with IngressClient(port=ing.port) as cli:
                via_wire = [cli.verify(it) for it in items]
    assert not direct[0].ok and all(r.ok for r in direct[1:])
    for w, d in zip(via_wire, direct):
        assert (w.ok, w.error, w.script_error) == (
            d.ok, d.error, d.script_error,
        )
    # Where a served request's time goes, outside the verify server: both
    # ingress stages once a request (a refused input is a verdict frame
    # too), each a positive stretch.
    for st, (count0, sum0) in stages0.items():
        count, total = _stage(st)
        assert count - count0 == len(items), st
        assert total > sum0, st
    # The worker's waits: one blocking take a burst (and the one that the
    # close ends), one non-blocking take a batch it handed the driver;
    # four requests one by one are four bursts of one batch.
    assert _span_count("serving.idle") - spans0["serving.idle"] == len(items) + 1
    assert _span_count("serving.take") - spans0["serving.take"] == len(items)


@pytest.mark.parametrize("delay_s", [0.0, 0.02])
def test_ingress_stages_observed_once_a_verdict_frame(delay_s):
    """`decode` when submit returned, `respond` when the verdict frame was
    written; resolved inside submit (on the loop's thread) or later on
    another thread, as the worker does. A shed request took neither."""
    stub = _StubVerify(delay_s=delay_s)
    item = BatchItem(b"tx", 0, 0)
    before = {st: _stage(st) for st in ("decode", "respond")}
    with IngressServer(stub, idle_s=10.0) as ing:
        with IngressClient(port=ing.port) as cli:
            for _ in range(3):
                assert cli.verify(item).ok
            stub.shed_reason = "slo"
            with pytest.raises(OverloadError):
                cli.verify(item)
    for st, (count0, sum0) in before.items():
        count, total = _stage(st)
        assert count - count0 == 3, st
        assert total > sum0
    if delay_s:  # the hop from the settling thread is inside `respond`
        assert _stage("respond")[1] - before["respond"][1] > 0


def test_shed_arrives_as_overloaded_frame_session_survives():
    stub = _StubVerify(shed_reason="slo")
    with IngressServer(stub, idle_s=10.0) as ing:
        with IngressClient(port=ing.port) as cli:
            item = BatchItem(b"tx", 0, 0)
            with pytest.raises(OverloadError) as ei:
                cli.verify(item)
            assert ei.value.reason == "slo"
            assert ei.value.code == Error.ERR_OVERLOADED
            # The session survived the shed: stop shedding, same
            # connection serves the retry.
            stub.shed_reason = None
            assert cli.verify(item).ok


def test_deadline_reaps_stalled_session():
    stub = _StubVerify()
    reaps0 = ingress_mod._I_REAPS.value()
    with IngressServer(stub, idle_s=0.2) as ing:
        sock = socket.create_connection(("127.0.0.1", ing.port), timeout=5)
        # Slow-loris: start a frame, never finish it.
        sock.sendall(bytes([FRAME_REQ]) + (100).to_bytes(4, "big") + b"ab")
        sock.settimeout(5)
        assert sock.recv(1) == b""  # server reaped us
        sock.close()
        assert ingress_mod._I_REAPS.value() == reaps0 + 1
        # The listener survived: a well-behaved client still verifies.
        with IngressClient(port=ing.port) as cli:
            assert cli.verify(BatchItem(b"tx", 0, 0)).ok


def test_oversized_frame_typed_error_then_close():
    stub = _StubVerify()
    errs0 = ingress_mod._I_PROTO_ERRS.value()
    with IngressServer(stub, idle_s=5.0, max_frame=1024) as ing:
        sock = socket.create_connection(("127.0.0.1", ing.port), timeout=5)
        sock.sendall(bytes([FRAME_REQ]) + (2048).to_bytes(4, "big"))
        ftype, payload = _recv_frame(sock)
        assert ftype == FRAME_ERR
        rid, code, _reason = decode_error_payload(payload)
        assert (rid, code) == (0, ERR_PROTO_OVERSIZED)
        assert sock.recv(1) == b""  # session closed
        sock.close()
    assert ingress_mod._I_PROTO_ERRS.value() == errs0 + 1


def test_garbage_frames_typed_error_then_close():
    stub = _StubVerify()
    with IngressServer(stub, idle_s=5.0) as ing:
        # Unknown frame type.
        s1 = socket.create_connection(("127.0.0.1", ing.port), timeout=5)
        s1.sendall(encode_frame(0x7F, b"junk"))
        ftype, payload = _recv_frame(s1)
        assert ftype == FRAME_ERR
        assert decode_error_payload(payload)[1] == ERR_PROTO_BAD_TYPE
        assert s1.recv(1) == b""
        s1.close()
        # REQ frame with garbage payload.
        s2 = socket.create_connection(("127.0.0.1", ing.port), timeout=5)
        s2.sendall(encode_frame(FRAME_REQ, b"\xff\xfe\xfd"))
        ftype, payload = _recv_frame(s2)
        assert ftype == FRAME_ERR
        assert decode_error_payload(payload)[1] == ERR_PROTO_MALFORMED
        assert s2.recv(1) == b""
        s2.close()
        # Truncated frame (header promises more than ever arrives, then
        # disconnect): counted, no crash, listener fine.
        errs0 = ingress_mod._I_PROTO_ERRS.value()
        s3 = socket.create_connection(("127.0.0.1", ing.port), timeout=5)
        s3.sendall(bytes([FRAME_REQ]) + (64).to_bytes(4, "big") + b"half")
        s3.close()
        deadline = time.monotonic() + 5
        while (ingress_mod._I_PROTO_ERRS.value() < errs0 + 1
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert ingress_mod._I_PROTO_ERRS.value() >= errs0 + 1
        with IngressClient(port=ing.port) as cli:
            assert cli.verify(BatchItem(b"tx", 0, 0)).ok


def test_graceful_drain_flushes_inflight_responses():
    stub = _StubVerify(delay_s=0.3)
    ing = IngressServer(stub, idle_s=10.0)
    ing.start()
    sock = socket.create_connection(("127.0.0.1", ing.port), timeout=5)
    item = BatchItem(b"tx", 0, 0)
    sock.sendall(encode_frame(FRAME_REQ, encode_request(5, "t", item)))
    # Wait until the request is submitted (settles 0.3s later), then
    # close: drain must hold the session open until the response flushes.
    deadline = time.monotonic() + 5
    while not stub.submitted and time.monotonic() < deadline:
        time.sleep(0.01)
    assert stub.submitted
    ing.close(drain=True)
    ftype, payload = _recv_frame(sock)
    assert ftype == FRAME_RESP
    rid, res = decode_response_payload(payload)
    assert rid == 5 and res.ok
    assert sock.recv(1) == b""  # and THEN the session closed
    sock.close()


def test_ingress_close_idempotent():
    stub = _StubVerify()
    ing = IngressServer(stub)
    ing.start()
    ing.close()
    ing.close()  # second close: no-op, no error


# -- fault sites -------------------------------------------------------


def test_read_fault_tears_down_one_session_only():
    stub = _StubVerify()
    with IngressServer(stub, idle_s=5.0) as ing:
        plan = FaultPlan(
            [FaultSpec(site="ingress.read", kind="raise", count=1)]
        )
        with inject(plan, seed=0) as inj:
            with IngressClient(port=ing.port) as cli:
                with pytest.raises(ConnectionError):
                    cli.verify(BatchItem(b"tx", 0, 0))
        assert inj.fired[("ingress.read", "raise")] == 1
        # Fault drained: a fresh session (lazy reconnect) verifies.
        with IngressClient(port=ing.port) as cli:
            assert cli.verify(BatchItem(b"tx", 0, 0)).ok


def test_write_fault_retry_client_recovers():
    stub = _StubVerify()
    with IngressServer(stub, idle_s=5.0) as ing:
        cli = IngressClient(port=ing.port)
        plan = FaultPlan(
            [FaultSpec(site="ingress.write", kind="raise", count=1)]
        )
        with inject(plan, seed=0) as inj:
            # The response write faults -> disconnect -> one retry on a
            # fresh connection succeeds.
            res = verify_with_retry(
                cli, BatchItem(b"tx", 0, 0), retries=3, backoff_s=0.01
            )
        assert res.ok
        assert inj.fired[("ingress.write", "raise")] == 1
        cli.close()


# -- retry classification ----------------------------------------------


class _ScriptedClient:
    """Transport stub: raises/returns a scripted sequence from verify()."""

    def __init__(self, script):
        self.script = list(script)
        self.calls = 0

    def verify(self, item, tenant="default"):
        self.calls += 1
        ev = self.script.pop(0)
        if isinstance(ev, BaseException):
            raise ev
        return ev


def test_retry_classification_shed_then_disconnect_then_ok():
    ok = BatchResult.success()
    cli = _ScriptedClient(
        [OverloadError("slo"), ConnectionError("reset"), ok]
    )
    res = verify_with_retry(
        cli, BatchItem(b"tx", 0, 0), retries=4, backoff_s=0.001,
        max_backoff_s=0.002,
    )
    assert res is ok and cli.calls == 3


def test_retry_never_retries_protocol_errors():
    cli = _ScriptedClient(
        [IngressProtocolError(ERR_PROTO_MALFORMED, "bad frame")]
    )
    with pytest.raises(IngressProtocolError):
        verify_with_retry(
            cli, BatchItem(b"tx", 0, 0), retries=4, backoff_s=0.001
        )
    assert cli.calls == 1  # no second attempt


def test_retry_budget_exhausted_reraises():
    cli = _ScriptedClient([OverloadError("slo")] * 3)
    with pytest.raises(OverloadError):
        verify_with_retry(
            cli, BatchItem(b"tx", 0, 0), retries=2, backoff_s=0.001,
            max_backoff_s=0.002,
        )
    assert cli.calls == 3  # initial + 2 retries

    cli2 = _ScriptedClient([ConnectionError("reset")] * 3)
    with pytest.raises(ConnectionError):
        verify_with_retry(
            cli2, BatchItem(b"tx", 0, 0), retries=2, backoff_s=0.001,
            max_backoff_s=0.002,
        )
    assert cli2.calls == 3
