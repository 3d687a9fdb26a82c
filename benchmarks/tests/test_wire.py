"""The benchmark's own framing against the program's codec, both ways."""

from benchmarks.harness import wire


def test_request_frames_decode_in_the_program():
    from bitcoinconsensus_tpu.serving import ingress

    outs = [(5000, b"\x00\x14" + b"\x11" * 20), (7000, b"\x51\x20" + b"\x22" * 32)]
    frame = wire.encode_request(77, "tenant-3", b"\x02rawtx", 1, 0x1F, spent_outputs=outs)
    ftype, ln = ingress.decode_header(frame[:5])
    assert (ftype, ln) == (ingress.FRAME_REQ, len(frame) - 5)
    rid, tenant, item = ingress.decode_request(frame[5:])
    assert (rid, tenant) == (77, "tenant-3")
    assert (item.spending_tx, item.input_index, item.flags) == (b"\x02rawtx", 1, 0x1F)
    assert item.spent_output_script is None and list(item.spent_outputs) == outs
    legacy = wire.encode_request(1, "t", b"tx", 0, 3, amount=9, script=b"\x51")
    _, _, item = ingress.decode_request(legacy[5:])
    assert (item.amount, item.spent_output_script, item.spent_outputs) == (9, b"\x51", None)


def test_response_and_error_frames_decode_here():
    from bitcoinconsensus_tpu.api import Error
    from bitcoinconsensus_tpu.core.script_error import ScriptError
    from bitcoinconsensus_tpu.models.batch import BatchResult
    from bitcoinconsensus_tpu.serving import ingress

    ok = ingress.encode_frame(ingress.FRAME_RESP, ingress.encode_response(5, BatchResult.success()))
    assert wire.decode_header(ok[:5]) == (wire.FRAME_RESP, len(ok) - 5)
    rid, good, err, se = wire.decode_response(ok[5:])
    assert (rid, good, err) == (5, True, int(Error.ERR_OK))
    bad = ingress.encode_response(6, BatchResult(False, Error.ERR_SCRIPT, ScriptError.EVAL_FALSE))
    assert wire.decode_response(bad) == (6, False, int(Error.ERR_SCRIPT), int(ScriptError.EVAL_FALSE))
    e = ingress.encode_error(9, int(Error.ERR_OVERLOADED), "slo")
    assert wire.decode_error(e) == (9, int(Error.ERR_OVERLOADED), "slo")
