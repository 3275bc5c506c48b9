"""The socket KV transport backend — crc-framed payloads over TCP.

The codebase's first true cross-process network surface: each
transfer opens a one-shot loopback TCP connection, writes one
length-prefixed crc-framed request, and waits (deadline-bounded) for
one ack frame.  A stdlib server thread accepts connections and
dispatches frames into the shared exactly-once receiver
(:meth:`~.base.KVTransport._ingest`), so the dedup ledger, breaker,
and retry envelope are IDENTICAL to the in-process backend — only the
wire differs.

Wire format (one frame)::

    magic   b"KVTX"          4 bytes
    version u8               currently 1
    kind    u8               1=REQ  2=ACK  3=ERR
    hlen    u32 (big-endian) JSON header length
    blen    u64 (big-endian) raw body length
    crc     u32 (big-endian) zlib.crc32(header_bytes + body)
    header  hlen bytes       JSON
    body    blen bytes       concatenated raw leaf buffers

A REQ header carries ``peer`` / ``tid`` / ``meta`` plus the payload
geometry (``num_blocks``/``block_size``), the per-leaf crc dict, the
optional per-block crc sidecar, and a ``manifest`` of
``[name, dtype, shape]`` rows locating each leaf inside the body —
every cache leaf rides the same frame, int8 scale sidecars included.
An ACK header carries the handler's ack; an ERR header carries
``etype``/``message`` and maps application-level rejections
(``ValueError``/``MemoryError``) back to NATIVE exceptions at the
sender, so torn-payload semantics cross the wire unchanged.

Frame-level integrity is separate from payload-level integrity: a
frame whose crc fails, whose magic is wrong, or whose declared size
exceeds ``max_frame_bytes`` raises
:class:`~.base.TransportFrameError` and the connection closes with
NOTHING ingested (torn frames rejected whole, like torn payloads).
The sender sees a connection-class failure and retries — and the
dedup ledger makes the retry safe even if the frame died after
dispatch.

Reordering: TCP preserves byte order within a connection, and each
transfer uses its own connection, so cross-transfer reordering cannot
interleave frames — but :class:`FrameReader` is still a strict
incremental parser (split reads across frame boundaries are
reassembled; trailing garbage is a frame error), which the codec
units in ``tests/L0/test_transport.py`` pin directly.

When NOT to use this backend: same-process pools (the default
everywhere).  It exists for the cross-process topology and costs a
host serialize/deserialize round-trip per transfer plus a connection
setup.
"""

from __future__ import annotations

import json
import socket
import struct
import threading
import zlib
from typing import Optional

import numpy as np

from ...resilience.breaker import CircuitBreaker
from .base import (KVTransport, ReceiverLedger,
                   TransportConnectionError, TransportError,
                   TransportFrameError, TransportPolicy,
                   TransportTimeoutError, _PeerState)

__all__ = [
    "FrameReader",
    "KIND_ACK",
    "KIND_ERR",
    "KIND_REQ",
    "MAX_FRAME_BYTES",
    "SocketTransport",
    "decode_payload",
    "encode_frame",
    "encode_payload",
]

MAGIC = b"KVTX"
VERSION = 1
KIND_REQ, KIND_ACK, KIND_ERR = 1, 2, 3
# 64 MiB default ceiling: a warm/hand-off payload at serving scale is
# a few MiB; anything bigger is a corrupt length field, not a payload
MAX_FRAME_BYTES = 64 << 20

_PRELUDE = struct.Struct(">4sBBIQI")     # magic ver kind hlen blen crc


def encode_frame(kind: int, header: dict, body: bytes = b"") -> bytes:
    """One wire frame; ``header`` must be JSON-serializable (the
    socket backend never carries live objects — ``carries_objects``
    is False)."""
    try:
        hbytes = json.dumps(header, separators=(",", ":")).encode()
    except TypeError as e:
        raise TransportError(
            f"socket transport header is not JSON-serializable "
            f"({e}) — live objects cannot cross the wire") from e
    crc = zlib.crc32(body, zlib.crc32(hbytes))
    return _PRELUDE.pack(MAGIC, VERSION, kind, len(hbytes),
                         len(body), crc) + hbytes + body


class FrameReader:
    """Incremental frame parser: :meth:`feed` raw socket bytes in any
    split, get back complete ``(kind, header, body)`` frames.  Every
    malformation — bad magic, bad version, oversized declared length,
    crc mismatch, unparseable header — raises
    :class:`~.base.TransportFrameError` with nothing partially
    delivered; the caller closes the connection."""

    def __init__(self, max_frame_bytes: int = MAX_FRAME_BYTES):
        self.max_frame_bytes = max_frame_bytes
        self._buf = bytearray()

    def feed(self, data: bytes):
        self._buf.extend(data)
        frames = []
        while len(self._buf) >= _PRELUDE.size:
            magic, ver, kind, hlen, blen, crc = _PRELUDE.unpack_from(
                self._buf)
            if magic != MAGIC:
                raise TransportFrameError(
                    f"bad frame magic {bytes(magic)!r} "
                    f"(expected {MAGIC!r})")
            if ver != VERSION:
                raise TransportFrameError(
                    f"unsupported frame version {ver} "
                    f"(speak version {VERSION})")
            total = hlen + blen
            if total > self.max_frame_bytes:
                raise TransportFrameError(
                    f"frame of {total} bytes exceeds the "
                    f"{self.max_frame_bytes}-byte ceiling — corrupt "
                    f"length field or oversized payload; rejected "
                    f"whole, connection closed")
            if len(self._buf) < _PRELUDE.size + total:
                break                     # wait for more bytes
            start = _PRELUDE.size
            hbytes = bytes(self._buf[start:start + hlen])
            body = bytes(self._buf[start + hlen:start + total])
            del self._buf[:start + total]
            if zlib.crc32(body, zlib.crc32(hbytes)) != crc:
                raise TransportFrameError(
                    "frame crc mismatch — torn in flight; rejected "
                    "whole, nothing ingested")
            try:
                header = json.loads(hbytes)
            except ValueError as e:
                raise TransportFrameError(
                    f"frame header is not JSON ({e})") from e
            frames.append((kind, header, body))
        return frames


def _dtype_tag(dt) -> str:
    """Wire tag for a leaf dtype.  Standard numerics use the numpy
    byte-order string (``<f4``); extended ml_dtypes types (bfloat16 —
    the DEFAULT cache dtype — float8s, ...) register as numpy void
    records whose ``.str`` is ``<V2``, which would silently decode as
    non-numeric void on the far side, so they ride by NAME instead."""
    return dt.str if dt.kind != "V" else dt.name


def _resolve_dtype(tag: str) -> "np.dtype":
    """Inverse of :func:`_dtype_tag`.  Name tags resolve through
    ml_dtypes (jax's own extended-dtype registry); an unknown tag is a
    frame error, not a silent void reinterpretation."""
    try:
        dt = np.dtype(tag)
    except TypeError:
        dt = None
    if dt is not None and dt.kind != "V":
        return dt
    try:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, tag))
    except (ImportError, AttributeError, TypeError):
        raise TransportFrameError(
            f"manifest names unknown leaf dtype {tag!r}; rejected "
            f"whole, nothing ingested")


def encode_payload(payload: dict):
    """``(header_fields, body)``: the checksummed payload dict
    (``engine.export_blocks`` shape) flattened to a leaf manifest +
    one contiguous byte body.  Every leaf rides — K, V, and the int8
    pool's scale sidecars alike."""
    manifest, chunks = [], []
    for name in sorted(payload["leaves"]):
        arr = np.asarray(payload["leaves"][name])
        manifest.append([name, _dtype_tag(arr.dtype), list(arr.shape)])
        chunks.append(arr.tobytes())
    fields = {"num_blocks": int(payload["num_blocks"]),
              "block_size": int(payload["block_size"]),
              "manifest": manifest,
              "crc": {k: int(v) for k, v in payload["crc"].items()}}
    if payload.get("block_crc") is not None:
        fields["block_crc"] = {
            name: [int(c) for c in crcs]
            for name, crcs in payload["block_crc"].items()}
    return fields, b"".join(chunks)


def decode_payload(header: dict, body: bytes) -> dict:
    """Rebuild the payload dict from a REQ frame.  Leaf byte counts
    must tile the body exactly — a mismatch is a frame error (the crc
    already matched, so this is a corrupt manifest)."""
    leaves = {}
    off = 0
    for name, dtype, shape in header["manifest"]:
        dt = _resolve_dtype(dtype)
        n = dt.itemsize * int(np.prod(shape, dtype=np.int64)) \
            if shape else dt.itemsize
        if off + n > len(body):
            raise TransportFrameError(
                f"manifest overruns frame body at leaf {name!r} "
                f"({off + n} > {len(body)} bytes)")
        leaves[name] = np.frombuffer(
            body, dtype=dt, count=int(np.prod(shape, dtype=np.int64)),
            offset=off).reshape(shape).copy()
        off += n
    if off != len(body):
        raise TransportFrameError(
            f"frame body has {len(body) - off} trailing bytes the "
            f"manifest does not claim")
    out = {"num_blocks": header["num_blocks"],
           "block_size": header["block_size"],
           "leaves": leaves,
           "crc": {k: int(v) for k, v in header["crc"].items()}}
    if header.get("block_crc") is not None:
        out["block_crc"] = {
            name: [int(c) for c in crcs]
            for name, crcs in header["block_crc"].items()}
    return out


class SocketTransport(KVTransport):
    """Loopback-TCP backend: a stdlib server thread serves the
    locally-registered peers; ``send`` opens a one-shot connection
    (to a routed address, or back to the own server for local peers)
    per transfer.  Registered in the apexlint lock-discipline scope:
    the server thread reaches shared transport state only through
    :meth:`_dispatch`, which serializes on the transport lock."""

    backend = "socket"
    carries_objects = False

    def __init__(self, policy: Optional[TransportPolicy] = None, *,
                 host: str = "127.0.0.1",
                 max_frame_bytes: int = MAX_FRAME_BYTES):
        super().__init__(policy)
        self.max_frame_bytes = max_frame_bytes
        self._listener = socket.create_server((host, 0))
        self.address = self._listener.getsockname()
        self._thread = threading.Thread(
            target=self._serve, name="kv-transport-server", daemon=True)
        self._thread.start()

    def register_route(self, name: str, address) -> None:
        """Route ``name`` to another transport's server address (the
        cross-process shape).  The peer gets the full envelope —
        breaker, ledger for its OWN inbound — but no local handler."""
        pol = self.policy
        with self._lock:
            self._peers[name] = _PeerState(
                name=name, handler=None,
                breaker=CircuitBreaker(
                    failure_threshold=pol.breaker_failures,
                    recovery_time=pol.breaker_recovery_s,
                    clock=pol.clock),
                ledger=ReceiverLedger(pol.dedup_window),
                address=tuple(address))

    # -- sender ------------------------------------------------------------

    def _deliver(self, st, tid, meta, payload):
        fields, body = encode_payload(payload)
        header = dict(fields, peer=st.name, tid=tid, meta=meta)
        frame = encode_frame(KIND_REQ, header, body)
        addr = st.address or self.address
        # the per-attempt socket timeout; the retry envelope's
        # deadline bounds the whole send on top
        timeout = self.policy.deadline_s
        try:
            with socket.create_connection(addr,
                                          timeout=timeout) as conn:
                conn.sendall(frame)
                reader = FrameReader(self.max_frame_bytes)
                frames = []
                while not frames:
                    chunk = conn.recv(1 << 16)
                    if not chunk:
                        raise TransportConnectionError(
                            f"transfer {tid} to {st.name!r}: "
                            f"connection closed before the ack")
                    frames = reader.feed(chunk)
        except socket.timeout as e:
            raise TransportTimeoutError(
                f"transfer {tid} to {st.name!r} stalled past "
                f"{timeout}s") from e
        except TransportError:
            raise
        except OSError as e:
            raise TransportConnectionError(
                f"transfer {tid} to {st.name!r}: {e}") from e
        kind, hdr, _ = frames[0]
        if kind == KIND_ACK:
            return hdr.get("ack")
        if kind == KIND_ERR:
            etype, msg = hdr.get("etype"), hdr.get("message", "")
            # application-level rejections cross the wire as their
            # native types — consumer degradation paths must not be
            # able to tell the backends apart
            if etype == "ValueError":
                raise ValueError(msg)
            if etype == "MemoryError":
                raise MemoryError(msg)
            raise TransportError(
                f"peer {st.name!r} answered {etype}: {msg}")
        raise TransportFrameError(
            f"unexpected frame kind {kind} in ack position")

    # -- server ------------------------------------------------------------

    def _serve(self):
        # the accept loop is the documented lock-free path: it holds
        # no shared transport state beyond the listener handle, and
        # blocking in accept() under the lock would wedge every sender
        # apexlint: disable=lock-discipline
        listener = self._listener
        while True:
            try:
                conn, _ = listener.accept()
            except OSError:
                return                    # listener closed by close()
            threading.Thread(target=self._serve_conn, args=(conn,),
                             daemon=True).start()

    def _serve_conn(self, conn):
        # per-connection framing is connection-private state; shared
        # transport state is only reached via _dispatch (which takes
        # the transport lock) — the lock-discipline boundary
        # apexlint: disable=lock-discipline
        reader = FrameReader(self.max_frame_bytes)
        with conn:
            while True:
                try:
                    chunk = conn.recv(1 << 16)
                except OSError:
                    return
                if not chunk:
                    return
                try:
                    frames = reader.feed(chunk)
                except TransportFrameError as e:
                    # torn/oversized frame: answer with a messaged
                    # error, ingest nothing, close the connection
                    try:
                        conn.sendall(encode_frame(
                            KIND_ERR,
                            {"etype": "TransportFrameError",
                             "message": str(e)}))
                    except OSError:
                        pass
                    return
                for kind, header, body in frames:
                    try:
                        conn.sendall(self._dispatch(kind, header,
                                                    body))
                    except OSError:
                        return

    def _dispatch(self, kind, header, body) -> bytes:
        """One REQ frame -> one ACK/ERR frame.  Every touch of shared
        transport state (peer registry, dedup ledger, counters)
        happens under the transport lock — the server thread's only
        entry into it."""
        with self._lock:
            if kind != KIND_REQ:
                return encode_frame(
                    KIND_ERR, {"etype": "TransportFrameError",
                               "message": f"unexpected frame kind "
                                          f"{kind}"})
            st = self._peers.get(header.get("peer"))
            if st is None or st.handler is None:
                return encode_frame(
                    KIND_ERR,
                    {"etype": "TransportError",
                     "message": f"no local handler for peer "
                                f"{header.get('peer')!r}"})
            try:
                payload = decode_payload(header, body)
                ack = self._ingest(st, int(header["tid"]),
                                   header.get("meta") or {}, payload)
            except (ValueError, MemoryError) as e:
                return encode_frame(
                    KIND_ERR, {"etype": type(e).__name__,
                               "message": str(e)})
            except TransportError as e:
                return encode_frame(
                    KIND_ERR, {"etype": type(e).__name__,
                               "message": str(e)})
            except Exception as e:   # noqa: BLE001 — a handler crash
                # must answer the sender (who degrades immediately),
                # not kill this thread and leave it waiting out its
                # whole deadline on a silent connection
                return encode_frame(
                    KIND_ERR, {"etype": type(e).__name__,
                               "message": str(e)})
            return encode_frame(
                KIND_ACK, {"tid": int(header["tid"]), "ack": ack})

    def close(self) -> None:
        try:
            self._listener.close()
        except OSError:
            pass
        self._thread.join(timeout=2.0)
