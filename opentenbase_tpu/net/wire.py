"""Wire protocol: length-prefixed pickled messages with CRC.

Reference analog: the pooler's unix-socket protocol (poolcomm.c) and the
extended libpq vocabulary between nodes (pgxcnode.c).  Numpy arrays pickle
efficiently (buffer protocol), which covers plan fragments, column batches,
and control messages with one frame format.

Close semantics: a peer that disconnects AT a message boundary is a clean
hangup — ``recv_msg`` returns None and server loops exit quietly.  A peer
that disconnects anywhere else (mid-frame, or while it still owes a reply)
is a failure — ``WireError``.  Callers that just sent a request pass
``expect_reply=True`` so the two cases are never conflated: "no message"
is only a valid answer when no message was owed.

Chaos hooks: call sites may pass a named fault point (``fault=``); when a
test armed that point via ``utils/faultinject.arm_wire`` the configured
connection fault (drop/delay/close/garble) fires here, at the exact
boundary a real network failure would hit.

Trace context: distributed tracing (obs/xray.py) rides inside the message
dict under the reserved ``"_xray"`` key — requests carry ``{"tid": ...}``
injected by clients, replies carry ``{"tid", "span"}`` piggy-backed by
servers.  The frame format itself is unchanged: peers that predate (or
disable) tracing simply ignore the key, so the protocol stays backward
and forward compatible with no version negotiation.
"""

from __future__ import annotations

import pickle
import socket
import struct
import time
import zlib

from ..utils import faultinject as FI

_HDR = struct.Struct("<II")  # length, crc32
MAX_MSG = 1 << 31


class WireError(ConnectionError):
    pass


def _apply_send_fault(sock: socket.socket, point: str,
                      blob: bytes):
    """Returns the (possibly corrupted) payload to send, or None to
    drop the message entirely.  'close' tears the socket down and
    raises, as a mid-send RST would."""
    act = FI.wire_action(point)
    if act is None:
        return blob
    mode = act["mode"]
    if mode == "delay":
        time.sleep(act["delay_s"])
        return blob
    if mode == "drop":
        return None
    if mode == "close":
        try:
            sock.close()
        except OSError:
            pass
        raise WireError(f"injected connection close at {point}")
    # garble: corrupt payload bytes but send the ORIGINAL header, so
    # the receiver sees a checksum mismatch (torn frame, bit rot)
    bad = bytearray(blob)
    if bad:
        bad[len(bad) // 2] ^= 0xFF
    return bytes(bad)


def send_msg(sock: socket.socket, obj, fault: str = None) -> int:
    """Encode and send one frame; returns the payload's bytes."""
    blob = pickle.dumps(obj, protocol=4)
    hdr = _HDR.pack(len(blob), zlib.crc32(blob))
    if fault is not None:
        blob = _apply_send_fault(sock, fault, blob)
        if blob is None:
            return 0            # dropped: peer waits, deadline fires
    sock.sendall(hdr + blob)
    return len(blob)


def _recv_exact(sock: socket.socket, n: int, expect: bool = False) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            if buf:
                raise WireError("connection closed mid-message")
            if expect:
                # the peer owed us a frame (we just sent a request):
                # a clean close here is still a broken conversation
                raise WireError("connection closed awaiting reply")
            return b""
        buf.extend(chunk)
    return bytes(buf)


def recv_msg(sock: socket.socket, expect_reply: bool = False,
             fault: str = None):
    """Receive one frame and decode it.  Returns None on a clean close
    at a message boundary — unless ``expect_reply`` is set, in which
    case a close is a WireError (the caller just sent a request and is
    owed an answer)."""
    blob = recv_frame(sock, expect_reply, fault)
    return None if blob is None else decode_msg(blob)


def decode_msg(blob: bytes):
    return pickle.loads(blob)


def recv_frame(sock: socket.socket, expect_reply: bool = False,
               fault: str = None):
    """Wait for one whole frame and return its checked payload, still
    encoded (None on a clean close, as `recv_msg`): a server that times
    a statement from the arrival of its message decodes it itself."""
    if fault is not None:
        act = FI.wire_action(fault)
        if act is not None:
            if act["mode"] == "delay":
                time.sleep(act["delay_s"])
            else:               # close/drop/garble on the recv side all
                try:            # present as a torn connection
                    sock.close()
                except OSError:
                    pass
                raise WireError(f"injected connection close at {fault}")
    hdr = _recv_exact(sock, _HDR.size, expect=expect_reply)
    if not hdr:
        return None
    length, crc = _HDR.unpack(hdr)
    if length > MAX_MSG:
        raise WireError(f"message too large: {length}")
    # the body is always mid-message: an EOF here can never mean "no
    # message" (satellite of ISSUE 8 — previously conflated with the
    # boundary case and surfaced as a generic short read)
    blob = _recv_exact(sock, length, expect=True)
    if zlib.crc32(blob) != crc:
        raise WireError("message checksum mismatch")
    return blob
