"""THE Python mirror of the native wire protocol constants.

``ps/native/kv_protocol.h`` is the single C++ definition of the KV
frame layout; this module is its single PYTHON definition.  Every
Python site that frames, parses, or reasons about KV wire bytes — the
ctypes client (:mod:`distlr_tpu.ps.client`), the codec reference
(:mod:`distlr_tpu.compress.codecs`), the chaos proxy's frame parser
(:mod:`distlr_tpu.chaos.proxy`), the membership coordinator
(:mod:`distlr_tpu.ps.membership`) — imports the names from HERE instead
of hand-copying values.  Hand-mirroring is exactly how the repo grew
wire-constant drift bugs (kStats length pins, a third hand-rolled copy
of the reply framing); the wire-parity lint
(``python -m distlr_tpu.analysis``) cross-checks this module against
the header and fails the build on any disagreement, one-sided constant,
or raw re-inlined literal in a mirror site.

Deliberately import-free (stdlib ``struct`` only): the chaos proxy and
the membership coordinator are control-plane and must stay jax-free and
cheap to import.

These constants are also what the executable protocol SPEC
(:mod:`distlr_tpu.analysis.protocol.spec`) is written against: the
model checker's op/flag/capability identities — and therefore every
invariant it proves — resolve through this module, so a drifted
constant fails wire parity before it can mis-model the protocol.
"""

from __future__ import annotations

import struct

#: frame magic (kv_protocol.h kMagic)
MAGIC = 0xD157C0DE

# --- Op codes (enum class Op) ------------------------------------------
OP_PUSH = 1
OP_PULL = 2
OP_BARRIER = 3
OP_SHUTDOWN = 4
OP_HELLO = 5
OP_STATS = 6
OP_PUSH_PULL = 7
OP_EPOCH = 8

# --- Flags bits (enum Flags) -------------------------------------------
FLAG_NONE = 0
FLAG_RESPONSE = 1
FLAG_ERROR = 2
FLAG_INIT_PUSH = 4
FLAG_FORCE_INIT = 8
#: bits 4-5 carry the gradient codec of a push-class value payload
CODEC_SHIFT = 4
CODEC_MASK = 0x30
#: the op addresses FTRL z/n accumulators (2x vals per key)
FLAG_OPT_STATE = 64
#: a 16-byte TraceFrame trailer follows the header (before the keys)
FLAG_TRACED = 128

# --- gradient wire codecs (enum Codec) ---------------------------------
CODEC_NONE = 0
CODEC_INT8 = 1
CODEC_SIGN = 2
#: not a compression but a carrier: the frame's float32 values cross in
#: the connection's shared mapping, none on the socket (kv_protocol.h
#: "values in a mapping": same-host, direct connections that attached)
CODEC_MAPPED = 3

#: value payloads under this many bytes stay on the socket (kMappedMinBytes)
MAPPED_MIN_BYTES = 65536
#: the mapping's header and the alignment of its two areas
MAPPED_HEADER_BYTES = 4096
#: a mapped kHello's aux: the attach's two steps (ask, confirm)
MAPPED_ASK = 1
MAPPED_CONFIRM = 2

#: int8 block-quantization granularity, values per f32 scale (kQuantBlock)
QUANT_BLOCK = 256

# --- kHello capability bits --------------------------------------------
CAP_CODEC_INT8 = 1 << CODEC_INT8
CAP_CODEC_SIGN = 1 << CODEC_SIGN
CAP_TRACE = 1 << 8
CAP_EPOCH = 1 << 9
#: the server shares a mapping with a same-host client that asks
CAP_MAPPED = 1 << 10

# --- kStats reply shape ------------------------------------------------
#: the original six integer counters every vintage replies (kStatsValsV1)
STATS_VALS_V1 = 6
#: current stats count: v1 six + 4 per-handler CPU seconds + epoch + the
#: BSP barrier's four (rounds, hold, spread, release CPU) + run_frames +
#: lock_wait_seconds + the release's two (fanned replies, wall) + a
#: push's five phases (recv, merge, sync wait, release apply, reply write)
#: + mapped_frames + FTRL's three (steps, steps that left an exact zero,
#: steps taken four at a time)
STATS_VALS = 28

#: wire-corruption guard for vals_per_key (kMaxValsPerKey)
MAX_VALS_PER_KEY = 4096

#: the 16-bit MsgHeader::aux field's ceiling — barrier generation ids
#: and membership epochs both ride it, so both are capped here (the
#: header has no named constant; this pins the u16 wire width)
AUX_MAX = 0xFFFF

# --- frame structs -----------------------------------------------------
#: MsgHeader wire layout: magic u32, op u8, flags u8, aux u16,
#: client_id u32, timestamp u32, num_keys u64 — little-endian, packed
HEADER_STRUCT = struct.Struct("<IBBHIIQ")
#: static_assert(sizeof(MsgHeader) == 24) twin
HEADER_SIZE = 24

#: TraceFrame trailer: trace_id u64, span_id u64
TRACE_FRAME_STRUCT = struct.Struct("<QQ")
#: static_assert(sizeof(TraceFrame) == 16) twin
TRACE_FRAME_SIZE = 16

# The struct formats must agree with the asserted C sizes — checked at
# import so a format edit can never ship a silently-misframed parser
# (the lint re-checks both against the header's static_asserts).
assert HEADER_STRUCT.size == HEADER_SIZE
assert TRACE_FRAME_STRUCT.size == TRACE_FRAME_SIZE


def codec_of(flags: int) -> int:
    """Codec id of a push-class frame's flags (native ``CodecOf``)."""
    return (flags & CODEC_MASK) >> CODEC_SHIFT


def codec_payload_bytes(codec: int, n: int) -> int:
    """Exact value-payload bytes of a coded frame carrying ``n`` values
    (native ``CodecPayloadBytes`` — both sides derive the size from
    ``(codec, n)``, so coded frames need no extra length field)."""
    if codec == CODEC_MAPPED:
        return 0  # the values are in the mapping, not on the socket
    if codec == CODEC_INT8:
        return ((n + QUANT_BLOCK - 1) // QUANT_BLOCK) * 4 + n
    if codec == CODEC_SIGN:
        return (n + 7) // 8
    return 4 * n
