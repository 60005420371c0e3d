"""Measured wire transport for federated adapter exchange.

The analytic cost model in :mod:`repro.core.costs` counts *parameters*; this
module puts actual **bytes** on a (simulated) wire so the two can be
cross-checked per round.  Three pieces:

* :class:`Codec` — pluggable array serialization (``fp32`` exact cast,
  ``bf16`` half-precision cast, ``int8`` symmetric per-tensor quantization),
  registered via :func:`register_codec` / built via :func:`make_codec`;
* :class:`AdapterPayload` — one serialized adapter tree: per-leaf encoded
  blocks plus the measured total byte size.  Packing honours the
  aggregator's *wire set* (``wire_arrays``: FFA sends only ``B``) and, for
  downlinks, the recorded per-layer ranks (rank-``p_l`` layers ship only
  their first ``p_l`` columns — zero padding never travels);
* :class:`Transport` — the round-trip used by the trainer: encode → count
  bytes → decode.  With the default ``fp32`` codec the round-trip is
  bit-exact, so the runtime reproduces the legacy loop; lossy codecs
  degrade exactly what a real deployment would (the wire tensors): clients
  resume from the decoded broadcast, and merge-into-base methods (FLoRA)
  fold the decoded stack into the base, while pure-broadcast methods still
  evaluate the server's exact aggregate.

``scale`` never travels: it is an O(L) header re-derived locally, and the
analytic model ignores it too, which keeps ``bytes == bytes_per_param ×
params`` an exact identity for the cast codecs.

**DP-on-the-wire**: with ``dp_clip``/``dp_sigma`` set, the uplink runs the
local Gaussian mechanism as a codec *stage* — the client's update delta
(trained − init) is clipped to L2 ≤ C and noised with std σ·C *before*
encoding, so the bytes on the wire are already privatized and the byte
accounting is unchanged (clip/noise don't alter shapes).  The noise key is
derived deterministically from ``(dp_seed, round, client_id)``, so runs
reproduce and no two uploads share a key.  This replaces the old
server-side noising sidecar in ``federated.py`` — privacy composes with
any codec, per-method byte accounting intact.

**Hardening** (PR 10): every :class:`EncodedArray` carries a CRC-32 of its
payload bytes (out-of-band — checksums don't count against the measured
wire bytes, keeping the ``bytes == bytes_per_param × params`` identity),
verified at :meth:`AdapterPayload.unpack_into` along with shape/layer/rank
contract checks against the receiving tree; structural violations raise
:class:`PayloadError` (or :class:`PayloadCorrupted` for checksum
mismatches) host-side instead of silently broadcasting a corrupted leaf.
The uplink retries corrupted payloads with deterministic exponential
backoff + jitter on the simulated clock and declares the client dead
(:class:`DeadClientError`) after ``max_retries`` re-sends; the DP stage
runs exactly once per upload, *before* the retry loop, so a re-encode
never re-clips or re-noises.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Any, Dict, List, Optional, Sequence, Tuple, Type

import jax
import ml_dtypes
import numpy as np

from repro.common import telemetry
from repro.core.aggregators.base import (adapter_leaf_paths,
                                         default_wire_arrays, get_path,
                                         set_path)

_BF16 = np.dtype(ml_dtypes.bfloat16)

#: rank axis of each wire tensor (A: rows are rank, B: columns are rank)
_RANK_AXIS = {"A": -2, "B": -1}


class PayloadError(ValueError):
    """A received payload violates the structural contract (shape, layer
    count, rank bound, or undecodable bytes) for the tree it targets."""


class PayloadCorrupted(PayloadError):
    """A received block's bytes do not match its CRC-32 checksum."""


class DeadClientError(RuntimeError):
    """A client's upload failed verification on every retry attempt."""

    def __init__(self, client_id: int, attempts: int, last: Exception):
        self.client_id, self.attempts, self.last = client_id, attempts, last
        super().__init__(f"client {client_id} declared dead after "
                         f"{attempts} failed upload attempts: {last}")


# ---------------------------------------------------------------------------
# codecs
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EncodedArray:
    """One serialized tensor: raw payload + the header needed to decode.

    ``crc`` is an optional CRC-32 of ``data``, attached at pack time and
    verified at unpack.  It is integrity metadata, not wire payload: the
    analytic cost model counts parameters, and checksums (like TCP/IP
    framing) live below that accounting, so ``num_bytes`` excludes them —
    the ``bytes == bytes_per_param × params`` identity is untouched.
    """
    data: bytes
    shape: Tuple[int, ...]
    meta: Tuple[float, ...] = ()
    crc: Optional[int] = None

    @property
    def num_bytes(self) -> int:
        # meta entries (e.g. a quantization scale) travel as fp32 headers
        return len(self.data) + 4 * len(self.meta)

    def verify(self) -> None:
        """Raise :class:`PayloadCorrupted` if the bytes don't match the
        checksum (no-op for unchecksummed blocks)."""
        if self.crc is not None and zlib.crc32(self.data) != self.crc:
            raise PayloadCorrupted(
                f"checksum mismatch on block shape={self.shape}: "
                f"crc32={zlib.crc32(self.data):#010x} != {self.crc:#010x}")


class Codec:
    """Array serializer.  ``decode(encode(x))`` returns fp32 numpy."""

    name: str = "?"
    bytes_per_param: float = 4.0

    def encode(self, arr: Any) -> EncodedArray:
        raise NotImplementedError

    def decode(self, enc: EncodedArray) -> np.ndarray:
        raise NotImplementedError


_CODECS: Dict[str, Type[Codec]] = {}


def register_codec(name: str):
    def deco(cls: Type[Codec]) -> Type[Codec]:
        _CODECS[name] = cls
        cls.name = name
        return cls
    return deco


def make_codec(name: str) -> Codec:
    try:
        return _CODECS[name]()
    except KeyError:
        raise ValueError(f"unknown codec {name!r} "
                         f"(registered: {sorted(_CODECS)})") from None


def available_codecs() -> List[str]:
    return sorted(_CODECS)


@register_codec("fp32")
class Fp32Codec(Codec):
    """Exact for fp32 inputs — the round-trip is the identity."""
    bytes_per_param = 4.0

    def encode(self, arr) -> EncodedArray:
        a = np.asarray(arr, np.float32)
        return EncodedArray(a.tobytes(), a.shape)

    def decode(self, enc: EncodedArray) -> np.ndarray:
        return np.frombuffer(enc.data, np.float32).reshape(enc.shape)


@register_codec("bf16")
class Bf16Codec(Codec):
    """Truncate-to-bfloat16 cast (the paper's 2-byte accounting)."""
    bytes_per_param = 2.0

    def encode(self, arr) -> EncodedArray:
        a = np.asarray(arr, np.float32).astype(_BF16)
        return EncodedArray(a.tobytes(), a.shape)

    def decode(self, enc: EncodedArray) -> np.ndarray:
        return np.frombuffer(enc.data, _BF16).reshape(enc.shape) \
            .astype(np.float32)


@register_codec("int8")
class Int8Codec(Codec):
    """Symmetric per-tensor int8 quantization with an fp32 scale header."""
    bytes_per_param = 1.0

    def encode(self, arr) -> EncodedArray:
        a = np.asarray(arr, np.float32)
        amax = float(np.max(np.abs(a))) if a.size else 0.0
        scale = amax / 127.0 if amax > 0 else 1.0
        q = np.clip(np.rint(a / scale), -127, 127).astype(np.int8)
        return EncodedArray(q.tobytes(), a.shape, (scale,))

    def decode(self, enc: EncodedArray) -> np.ndarray:
        q = np.frombuffer(enc.data, np.int8).reshape(enc.shape)
        return q.astype(np.float32) * np.float32(enc.meta[0])


# ---------------------------------------------------------------------------
# payloads
# ---------------------------------------------------------------------------


def _wire_fn(aggregator) -> Any:
    return getattr(aggregator, "wire_arrays", None) or default_wire_arrays


@dataclasses.dataclass
class AdapterPayload:
    """One adapter tree as it travels: per-leaf encoded blocks + size.

    ``blocks`` maps leaf path → wire-array name → per-layer
    :class:`EncodedArray` list (a single whole-array block when no ragged
    per-layer ranks were given).
    """

    codec: str
    blocks: Dict[Tuple, Dict[str, List[EncodedArray]]]
    num_bytes: int

    @classmethod
    def pack(cls, tree: Dict, codec: Codec, wire_fn=default_wire_arrays,
             ranks: Optional[Dict[Tuple, Sequence[int]]] = None,
             checksum: bool = True) -> "AdapterPayload":
        """Serialize ``tree``'s wire arrays.  With ``ranks`` (per-leaf,
        per-layer, as recorded in an :class:`AggResult`), layer ``l`` of a
        leaf ships only its first ``r_l`` rank rows/columns.

        All wire arrays leave the device in ONE ``jax.device_get`` (ragged
        per-layer slicing happens host-side on the fetched buffers), so
        packing costs one sync per payload, not one per tensor.  With
        ``checksum`` (default) every block carries a CRC-32 verified at
        :meth:`unpack_into`."""
        items: List[Tuple[Tuple, str, Any]] = []
        for path in adapter_leaf_paths(tree):
            leaf = get_path(tree, path)
            for name, arr in wire_fn(leaf).items():
                items.append((path, name, arr))
        host = jax.device_get([arr for (_, _, arr) in items])
        blocks: Dict[Tuple, Dict[str, List[EncodedArray]]] = {}
        total = 0
        for (path, name, _), arr in zip(items, host):
            axis = _RANK_AXIS.get(name)
            rs = ranks.get(path) if ranks else None
            if rs is None or axis is None:
                encs = [codec.encode(arr)]
            else:
                layers = arr if arr.ndim == 3 else arr[None]
                encs = []
                for l, r_l in enumerate(rs):
                    lay = layers[l]
                    cut = lay[:r_l, :] if axis == -2 else lay[:, :r_l]
                    encs.append(codec.encode(cut))
            if checksum:
                encs = [dataclasses.replace(e, crc=zlib.crc32(e.data))
                        for e in encs]
            blocks.setdefault(path, {})[name] = encs
            total += sum(e.num_bytes for e in encs)
        return cls(codec.name, blocks, total)

    def unpack_into(self, tree: Dict, codec: Codec,
                    verify: bool = True) -> Dict:
        """Rebuild a tree shaped like ``tree`` with every wire array
        replaced by its decoded bytes (non-wire entries, e.g. ``scale`` or a
        frozen ``A``, pass through from ``tree`` — they were never sent).
        Decoded leaves are host (numpy) arrays; downstream jnp ops move
        them to device on first use.

        With ``verify`` (default) every block's CRC-32 is checked before
        decoding and the decoded shapes are validated against the contract
        implied by ``tree``: a whole-array block must match the reference
        shape exactly; ragged per-layer blocks must cover exactly the
        reference layer count with per-layer ranks within the reference
        rank dimension.  Violations raise :class:`PayloadCorrupted` /
        :class:`PayloadError` host-side — a corrupted leaf is never
        silently broadcast into the aggregator."""
        out: Dict = {}
        for path in adapter_leaf_paths(tree):
            leaf = dict(get_path(tree, path))
            for name, encs in self.blocks[path].items():
                ref = leaf[name]
                if verify:
                    for enc in encs:
                        enc.verify()
                if len(encs) == 1 and encs[0].shape == tuple(ref.shape):
                    leaf[name] = _checked_decode(codec, encs[0], path, name)
                else:  # ragged per-layer blocks: zero-fill past each r_l
                    axis = _RANK_AXIS.get(name)
                    if verify and axis is None:
                        raise PayloadError(
                            f"{'/'.join(map(str, path))}:{name}: ragged "
                            f"blocks for a non-rank wire array")
                    ref_shape = (tuple(ref.shape) if ref.ndim == 3
                                 else (1,) + tuple(ref.shape))
                    if verify and len(encs) != ref_shape[0]:
                        raise PayloadError(
                            f"{'/'.join(map(str, path))}:{name}: "
                            f"{len(encs)} ragged layer blocks for "
                            f"{ref_shape[0]} layers")
                    layers = np.zeros(ref_shape, np.float32)
                    for l, enc in enumerate(encs):
                        dec = _checked_decode(codec, enc, path, name)
                        if verify:
                            _check_ragged(dec, ref_shape[1:], axis, path,
                                          name, l)
                        if axis == -2:
                            layers[l, :dec.shape[0], :] = dec
                        else:
                            layers[l, :, :dec.shape[1]] = dec
                    if ref.ndim != 3:
                        layers = layers[0]
                    leaf[name] = layers
            set_path(out, path, leaf)
        return out


def _checked_decode(codec: Codec, enc: EncodedArray, path: Tuple,
                    name: str) -> np.ndarray:
    """Decode one block, converting low-level buffer/reshape failures
    (truncated bytes, inconsistent header) into :class:`PayloadError`."""
    try:
        dec = codec.decode(enc)
    except (ValueError, TypeError) as e:
        raise PayloadError(f"{'/'.join(map(str, path))}:{name}: "
                           f"undecodable block: {e}") from e
    if tuple(dec.shape) != tuple(enc.shape):
        raise PayloadError(f"{'/'.join(map(str, path))}:{name}: decoded "
                           f"shape {dec.shape} != header {enc.shape}")
    return dec


def _check_ragged(dec: np.ndarray, layer_shape: Tuple[int, ...], axis: int,
                  path: Tuple, name: str, layer: int) -> None:
    """One ragged layer block must be the reference layer shape with the
    rank axis shortened to r_l ≤ full rank."""
    full = list(layer_shape)
    rank_dim = full[axis]
    got = list(dec.shape)
    ok = (len(got) == len(full) and got[axis] <= rank_dim
          and all(g == f for i, (g, f) in enumerate(zip(got, full))
                  if i != len(full) + axis))
    if not ok:
        raise PayloadError(
            f"{'/'.join(map(str, path))}:{name}[{layer}]: ragged block "
            f"shape {tuple(dec.shape)} violates layer contract "
            f"{tuple(layer_shape)} (rank axis {axis} ≤ {rank_dim})")


# ---------------------------------------------------------------------------
# the transport
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TransportStats:
    """Per-round uplink reliability counters (reset by the trainer)."""
    retries: int = 0
    crc_failures: int = 0
    dead_clients: int = 0


#: rng stream tag for retry-backoff jitter
_JITTER_TAG = 0xBACF


class Transport:
    """Measured client↔server wire: every exchanged adapter tree is
    serialized with the configured codec, its bytes are counted, and the
    *decoded* tree is what the receiving side actually uses.

    The uplink is an at-least-once channel: payloads are checksummed
    (``checksums``, default on), verification failures are retried up to
    ``max_retries`` times with deterministic exponential backoff —
    ``backoff_base · 2^attempt · (1 + backoff_jitter · u)`` with ``u``
    drawn from a pure function of ``(round, client, attempt)`` — advancing
    the simulated ``clock``, and a client whose every attempt fails is
    declared dead (:class:`DeadClientError`; the trainer treats it as a
    drop).  A ``fault_plan`` (see :mod:`.faults`) can corrupt attempts
    deterministically for testing.  Retransmissions count against the
    measured wire bytes (a real wire pays for them); checksums do not.
    """

    def __init__(self, codec: Any = "fp32", dp_clip: float = 0.0,
                 dp_sigma: float = 0.0, dp_seed: int = 0,
                 checksums: bool = True, max_retries: int = 3,
                 backoff_base: float = 0.1, backoff_jitter: float = 0.5,
                 fault_plan: Any = None, clock: Any = None):
        self.codec = codec if isinstance(codec, Codec) else make_codec(codec)
        self.dp_clip = float(dp_clip)
        self.dp_sigma = float(dp_sigma)
        self.dp_seed = int(dp_seed)
        self.checksums = bool(checksums)
        self.max_retries = int(max_retries)
        self.backoff_base = float(backoff_base)
        self.backoff_jitter = float(backoff_jitter)
        self.fault_plan = fault_plan
        self.clock = clock if clock is not None else (
            fault_plan.clock if fault_plan is not None else None)
        self.stats = TransportStats()

    def reset_stats(self) -> TransportStats:
        """Swap in fresh counters, returning the old ones."""
        old, self.stats = self.stats, TransportStats()
        return old

    def _dp_stage(self, adapters: Dict, init_adapters: Optional[Dict],
                  rnd: int, client_id: int) -> Dict:
        """Local DP on one upload: clip the update delta to L2 ≤ C, noise
        with std σ·C, re-anchor on the init.  Applied exactly once, before
        encoding."""
        if not (self.dp_clip or self.dp_sigma):
            return adapters
        from repro.core.privacy import (clip_update, local_gaussian_noise,
                                        tree_add, tree_sub)
        if init_adapters is None:
            raise ValueError("DP transport needs the round's init adapters "
                             "to form the update delta")
        clip = self.dp_clip or 1.0
        delta = tree_sub(adapters, init_adapters)
        delta, _ = clip_update(delta, clip)
        if self.dp_sigma:
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(self.dp_seed), rnd),
                client_id)
            delta = local_gaussian_noise(delta, self.dp_sigma, clip, key)
        return tree_add(init_adapters, delta)

    def client_to_server(self, adapters: Dict, aggregator, *,
                         init_adapters: Optional[Dict] = None,
                         rnd: int = 0, client_id: int = 0
                         ) -> Tuple[Dict, int]:
        """Uplink one trained client tree (through the DP stage when
        configured).  Returns (decoded tree, bytes across all attempts).

        Verification failures retry with deterministic backoff; raises
        :class:`DeadClientError` once ``max_retries`` re-sends have failed.
        The DP stage runs exactly once, before the first pack — a retry
        re-encodes the already-privatized tree, never re-clips/re-noises.
        """
        with telemetry.span("wire.up", client=client_id) as sp:
            decoded, nbytes = self._uplink(adapters, aggregator,
                                           init_adapters, rnd, client_id)
            sp.set(bytes=nbytes)
        return decoded, nbytes

    def _uplink(self, adapters: Dict, aggregator,
                init_adapters: Optional[Dict], rnd: int, client_id: int
                ) -> Tuple[Dict, int]:
        wire = _wire_fn(aggregator)
        adapters = self._dp_stage(adapters, init_adapters, rnd, client_id)
        total_bytes, last_err = 0, None
        for attempt in range(self.max_retries + 1):
            payload = AdapterPayload.pack(adapters, self.codec, wire,
                                          checksum=self.checksums)
            if self.fault_plan is not None and self.fault_plan.is_corrupt(
                    rnd, client_id, attempt):
                payload = self.fault_plan.corrupt_payload(
                    payload, rnd, client_id, attempt)
            total_bytes += payload.num_bytes
            try:
                decoded = payload.unpack_into(adapters, self.codec,
                                              verify=self.checksums)
                return decoded, total_bytes
            except PayloadError as e:
                last_err = e
                if isinstance(e, PayloadCorrupted):
                    self.stats.crc_failures += 1
                if attempt < self.max_retries:
                    self.stats.retries += 1
                    u = float(np.random.default_rng(
                        [_JITTER_TAG, rnd, client_id, attempt]).random())
                    delay = (self.backoff_base * 2 ** attempt
                             * (1.0 + self.backoff_jitter * u))
                    if self.clock is not None:
                        self.clock.advance(delay)
        self.stats.dead_clients += 1
        raise DeadClientError(client_id, self.max_retries + 1, last_err)

    def server_to_clients(self, agg, aggregator, num_receivers: int
                          ) -> Tuple[Optional[Dict], int]:
        """Downlink one round's result to ``num_receivers`` clients.

        Broadcast methods ship the global tree (ragged per-layer ranks —
        zero padding stays home) once per receiver; per-client methods
        (FlexLoRA) ship each tailored tree once.  Returns the decoded
        global tree (what clients resume from) and total downlink bytes.
        """
        with telemetry.span("wire.down") as sp:
            decoded, nbytes = self._downlink(agg, _wire_fn(aggregator),
                                             num_receivers)
            sp.set(bytes=nbytes)
        return decoded, nbytes

    def _downlink(self, agg, wire, num_receivers: int
                  ) -> Tuple[Optional[Dict], int]:
        if agg.per_client is not None:
            nbytes = sum(
                AdapterPayload.pack(t, self.codec, wire).num_bytes
                for t in agg.per_client)
            if agg.global_adapters is None:
                return None, nbytes
            payload = AdapterPayload.pack(agg.global_adapters, self.codec,
                                          wire)
            return payload.unpack_into(agg.global_adapters, self.codec), nbytes
        if agg.global_adapters is None:
            return None, 0
        payload = AdapterPayload.pack(agg.global_adapters, self.codec, wire,
                                      ranks=agg.ranks)
        decoded = payload.unpack_into(agg.global_adapters, self.codec)
        return decoded, payload.num_bytes * num_receivers


def make_transport(spec: Any, **kw) -> Transport:
    """Coerce a transport spec (instance | codec name | Codec) into a
    :class:`Transport`.  ``kw`` kwargs (``dp_clip``/``dp_sigma``/
    ``dp_seed``, plus the hardening knobs ``checksums``/``max_retries``/
    ``backoff_base``/``backoff_jitter``/``fault_plan``/``clock``)
    configure the built transport; an already-built instance is returned
    as-is (its own config wins)."""
    if isinstance(spec, Transport):
        return spec
    return Transport(spec or "fp32", **kw)
