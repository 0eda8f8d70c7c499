// Wire protocol for the distlr_tpu KV parameter server.
//
// TPU-native re-design of the ps-lite worker<->server RPC surface the
// reference links against (reconstructed API in SURVEY.md §2.2 E1.d-f:
// KVWorker::Push/Pull/Wait, KVServer with deferred Response, KVMeta.push
// discriminator, SArray<Key>/SArray<Val> payloads).  This replaces
// ZeroMQ + protobuf with a minimal length-prefixed binary framing over
// TCP (the DCN control/data plane; the on-chip sync path never touches
// this — it is lax.psum over ICI).
//
// Frame layout (little-endian, no padding):
//   MsgHeader { magic, op, flags, aux, client_id, timestamp, num_keys }
//   then num_keys * u64 keys
//   then (op == PUSH || (op == PULL && is_response))
//        num_keys * vals_per_key * f32 vals
//        (none on the socket where the header's codec field says
//        kCodecMapped: they stand in the connection's shared mapping,
//        see "values in a mapping" below)
//
// vals_per_key (the header's aux field for kPush/kPull/kPushPull;
// 0 == 1 == legacy scalar keys): each key addresses vals_per_key
// CONSECUTIVE slots of the flat parameter space, starting at
// key * vals_per_key — ps-lite's KVPairs.lens capability (uniform
// lens).  Two users: the row-blocked CTR path ships one u64 row id
// per R-lane table row instead of R expanded keys (the expanded
// encoding spends 8 bytes of key per 4 bytes of value; at R=32 the
// multi-val encoding cuts keyed wire bytes ~2.7x), and the client's
// default-key ops (a dense push, pull, push-pull or seed of the whole
// key space) address it as runs of the largest vals_per_key that
// divides dim and every range boundary (ps/client.py
// _dense_row_encoding; at D=1M over two servers 125 keys a server
// where the flat frame has 500,000).  The server never writes the flat
// keys out: its handlers walk the rows (kv_server.cc Rows), every slot
// getting the float32 operations, in the order, that a client's own
// expanded keys would have given it, so apply/merge/barrier/rollback
// semantics are byte-identical; a frame whose keys are one ascending
// consecutive run is handled as the one range of slots it is (kStats
// run_frames counts those).
//
// Semantics mirror the reference server handle (src/main.cc:41-96):
//   * first PUSH initializes server weights (src/main.cc:50-56)
//   * sync mode: PUSH responses are DEFERRED until num_workers pushes
//     arrive, then one SGD update is applied and all responses released
//     at once — the reply is the BSP barrier (src/main.cc:57-78)
//   * async mode: SGD applied per PUSH, reply immediate (src/main.cc:79-84)
//   * PULL replies the current weight slice (src/main.cc:85-95)
//   * BARRIER: counted per-group, released when num_workers reached
//     (Postoffice::Barrier equivalent, src/main.cc:150)

#ifndef DISTLR_TPU_PS_KV_PROTOCOL_H_
#define DISTLR_TPU_PS_KV_PROTOCOL_H_

#include <sys/mman.h>

#include <cmath>
#include <cstdint>
#include <cstring>

namespace distlr {

constexpr uint32_t kMagic = 0xD157C0DE;

enum class Op : uint8_t {
  kPush = 1,
  kPull = 2,
  kBarrier = 3,
  kShutdown = 4,
  kHello = 5,   // worker registration: client_id announces itself
  kStats = 6,   // health probe: response vals = server counters (see below)
  // Fused push+pull: the request carries gradient vals like kPush; the
  // reply carries the post-update weights for the SAME keys like a
  // kPull.  One round trip replaces the reference's two per batch
  // (src/lr.cc:116-132 pulls then pushes the full vector every step).
  // Async: apply immediately, reply fresh weights.  Sync: the reply is
  // deferred with the BSP round like any push — and when the barrier
  // releases, the payload is the post-round weights, which is exactly
  // what the worker's NEXT pull would have returned (rounds are totally
  // ordered), so the fused trajectory is bit-identical to pull+push.
  kPushPull = 7,
  // Membership epoch (the elastic-fleet round): the group layout —
  // which rank owns which key range — is versioned by a u16 epoch that
  // rides MsgHeader::aux, the same field (and the same released-
  // generation pattern) the barrier machinery already uses for its
  // generation ids.  Three forms:
  //   * ANNOUNCE (flags kNone, aux = E > 0): this connection expects
  //     layout epoch E.  From then on every keyed data op (push / pull
  //     / push_pull, incl. init forms) is FENCED: if the server's
  //     epoch differs, the op is answered — after its payload is fully
  //     read, so the stream stays framed — with an error frame whose
  //     op is kEpoch (not the echoed data op; that is what makes the
  //     fence unambiguous to the client) and whose aux carries the
  //     server's CURRENT epoch.  The client re-negotiates routing from
  //     the membership coordinator exactly the way it already re-runs
  //     kHello on reconnect; an in-flight push that straddled the flip
  //     is absorbed through the push-outcome-unknown path (some ranks
  //     may have applied their slices), never re-issued.
  //   * QUERY (flags kNone, aux = 0): no announcement; the reply's aux
  //     is the server's current epoch.
  //   * SET (flags kForceInit, aux = E): ADMIN — the membership
  //     coordinator flips the server to epoch E (the fence arming the
  //     drain window).  Replies aux = E.
  // Un-announced connections (legacy clients, supervisor probes, the
  // coordinator's own drain pulls/seeds) are never fenced — the
  // control plane must work THROUGH a migration, and a pre-epoch
  // client of a static group sees zero behavior change.  Epochs start
  // at 1; 0 means "not announced".
  kEpoch = 8,
};

// kStats response payload, in order: dim, initialized,
// pending_sync_pushes, barrier_waiters, total_pushes, total_pulls,
// then (since the continuous-profiling round) cumulative per-handler
// THREAD CPU seconds — cpu_push_seconds, cpu_pull_seconds,
// cpu_stats_seconds, cpu_barrier_seconds — measured with
// CLOCK_THREAD_CPUTIME_ID around each handler dispatch (payload read +
// decode + apply; blocked socket time never counts), so the Python
// side can mirror them as distlr_kv_server_cpu_seconds{handler} and a
// flamegraph's Python edge lines up with the C++ side.
// Each counter is a float64 (f32 would silently freeze counters at
// 2^24), transmitted as 2 Val slots via memcpy — so the response header
// carries num_keys == 2 * (stats replied).  Extension is ADDITIVE in
// BOTH directions: the request's aux field advertises how many stats
// the CLIENT accepts (0 from a pre-extension client — its aux was
// always zero), and the server replies min(aux, kStatsVals) but never
// fewer than the v1 six.  So an old client against a new server still
// gets exactly the 6-slot reply its strict length check demands, and a
// new client against an old server (which ignores aux and always sends
// six) reads what arrived — mixed vintages keep probing.
// The failure-detection hook the reference lacks entirely (SURVEY.md
// §5.3: a dead worker deadlocks the sync barrier forever with no
// diagnostic) — a supervisor polling kStats sees pending_sync_pushes
// stuck below num_workers and can name the straggler condition.
// Slot 10 (the membership round, additive like the CPU tail): the
// server's current layout EPOCH — so one health probe shows a mixed-
// epoch group mid-migration, and `distlr_ps_server_stat{stat="epoch"}`
// scrapes the flip.
// Slots 11-14 (the BSP barrier's tail, additive after `epoch`; zeros
// from an async server), kept under the lock the sync branch already
// holds: sync_rounds (rounds released), sync_hold_seconds (sum over
// released pushes of the wall from a push's arrival to its own reply
// written), sync_spread_seconds (sum over rounds of last arrival minus
// first), cpu_release_seconds (thread CPU of the release: apply, clear,
// W gathers and replies, on the last voter's thread and, for the
// replies it hands them, the server's writers; the same cycles also
// stand in cpu_push_seconds).
// Slot 15 (additive after the barrier's tail): run_frames, of the
// operations total_pushes and total_pulls count, those whose keys were
// one ascending consecutive run (keys[i] == keys[0] + i), handled as
// the range [keys[0]*vpk, (keys[0] + num_keys)*vpk) it is: one loop to
// apply or merge, one copy to reply.  A fused push-pull stands in both
// totals and so counts twice here; run_frames over the rise of the two
// totals is the share of a job's traffic on that path (1 for a dense
// worker's default-key ops, 0 for scattered keys).
// Slot 16 (additive after run_frames): lock_wait_seconds, the wall
// seconds the push handlers (kPush and kPushPull, every mode) stood
// waiting for the server's one lock, summed over total_pushes: near
// zero where pushes arrive apart, and the first thing a merge pays
// where W workers on W chips push at the same instant (each behind the
// others' merges, and all behind a release that is still answering).
// Slots 17 and 18 (additive after lock_wait_seconds; zeros from an
// async server), the release's fan-out: a round's replies that carry
// values (a fused push's post-round weights) are written side by side,
// one thread a reply, where the round holds more than one, each on a
// connection of its own; header-only replies stay on the releasing
// thread, which also writes one of the fused ones and holds the
// server's lock until the last is out.  release_fanned_replies counts
// the replies written by a thread other than the releasing one (W - 1
// a round of W fused pushes, 0 for header-only rounds): how often the
// fan-out engages.  release_wall_seconds is the wall from the last
// voter's merge done to the last reply written, summed over
// sync_rounds: the release's length, which the lock is held for.
// Slots 19-23 (additive after the release's two): a push's life on the
// server, phase by phase, in wall seconds on the monotonic clock (the
// clients' clock too, on one host), summed as named.  recv_seconds,
// over total_pushes: from a push's header read to its keys and values
// read and decoded (the bytes through the socket).  merge_seconds, over
// total_pushes: from the server's lock held to the push's own
// arithmetic done: the BSP merge; for an async or a seed push the apply
// and the reply's copy; the release is not in it.  sync_wait_seconds,
// over the pushes of released rounds (zero from an async server): from
// a push's merge done to its round's release begun, the wait for the
// later arrivals, 0 for the last voter; sync_hold_seconds less the
// merge less this is a reply's place in the release.
// release_apply_seconds, over sync_rounds (zero from an async server):
// from the release's begin (the last voter's merge done) to the mean
// applied and the merge buffer cleared; release_wall_seconds less this
// is the replies' writes.  reply_write_seconds, over the replies that
// carry values (a fused push's weights, BSP or async; header-only
// replies are not timed): from one reply's write begun to written,
// added by whichever thread wrote it, so a round's W replies written
// side by side count W writes; where the values cross in the mapping
// (kCodecMapped) a write is the copy into the reply area and the
// 24-byte header after it.
// Slot 24 (additive after a push's phases): mapped_frames, of the
// operations total_pushes and total_pulls count, those whose values
// crossed in the connection's shared mapping and not through the socket
// (kCodecMapped: the request's values read in place, the reply's
// written in place).  A fused push-pull stands in both totals and so
// counts twice here, as in run_frames; mapped_frames over the rise of
// the two totals is the share of a job's value-carrying traffic that
// stayed out of the kernel (1 for same-host workers of slices of
// kMappedMinBytes or more, 0 through a proxy or across hosts).
// Slots 25 and 26 (additive after mapped_frames; zeros from a server
// with no FTRL coordinate), counted where the per-coordinate
// FTRL-Proximal step runs (an async push's apply, a BSP release's apply
// of the round's mean; a zero gradient entry steps nothing and counts
// nothing): ftrl_steps, the coordinates a step ran on, and ftrl_zeroed,
// of those the steps whose |z| <= l1 branch left the weight exactly
// 0.0.  merge_seconds over the rise of ftrl_steps is an asynchronous
// FTRL server's nanoseconds a step (the apply of scattered rows and the
// reply's copy with it); ftrl_zeroed over ftrl_steps is the share of
// steps L1 took to zero.
// Slot 27 (additive after ftrl_zeroed; zero from a server with no FTRL
// coordinate): ftrl_packed_steps, of the steps ftrl_steps counts, those
// an asynchronous keyed push of single-value rows took in groups of four
// (kv_loops.h FtrlStepPacked: four keys strictly ascending, no entry
// 0.0, no --opt_segments boundary among them); the rest went a
// coordinate at a time.  ftrl_packed_steps over ftrl_steps is the share
// of the rule's work done four lanes wide.
constexpr uint64_t kStatsValsV1 = 6;
constexpr uint64_t kStatsVals = 28;

enum Flags : uint8_t {
  kNone = 0,
  kResponse = 1,
  kError = 2,
  // PUSH that seeds the weights IF the server is uninitialized and is a
  // no-op otherwise (always replied immediately, never counted toward
  // the sync merge).  Idempotent by design: a restarted worker re-sends
  // its init without corrupting state — without the flag, a re-sent
  // init lands in the async path as a bogus gradient.
  kInitPush = 4,
  // With kInitPush: seed UNCONDITIONALLY, overwriting live weights.
  // The checkpoint-resume path needs this against a surviving
  // (already-initialized) server group — a plain init would no-op and
  // training would silently resume from the servers' stale crash-time
  // weights while the epoch counter says otherwise.  Restarted workers
  // must NOT set it (they would roll peers back to the checkpoint).
  kForceInit = 8,
  // Bits 4-5: gradient CODEC of a push-class frame's value payload
  // (see Codec below; 0 = dense f32, the only encoding older peers
  // speak).  Landed additively like vals_per_key: the server decodes at
  // the parsing layer, so merge/barrier/rollback/optimizer semantics
  // are byte-identical to a client that sent dense f32.  A client may
  // set these bits ONLY after the kHello capability handshake proved
  // every server of the group decodes the codec — an un-negotiated
  // compressed frame against an old server would desynchronize the
  // stream (the old server reads num_keys*vpk f32s of payload).
  // The field's last value, kCodecMapped, is no compression but a
  // carrier: the frame's float32 values cross in the connection's
  // shared mapping ("values in a mapping" below), on a pull and on a
  // reply as on a push, and only after that connection's attach.
  kCodecShift = 4,
  kCodecMask = 0x30,
  // The op addresses the server optimizer's per-coordinate accumulator
  // state (FTRL z/n) instead of the weights: a kPull|kOptState reply
  // carries 2x vals per key ([z..., n...]); a kPush|kInitPush|kOptState
  // request seeds them the same way.  This is what lets a supervisor
  // snapshot/restore an FTRL rank without degrading a respawn to a
  // warm restart (weights-only reseed loses the accumulators).  Only
  // valid with kInitPush on the push side — optimizer state has no
  // gradient semantics to merge.
  kOptState = 64,
  // Bit 7: the request frame carries a 16-byte TraceFrame (trace_id,
  // span_id — Dapper-style distributed-trace propagation) immediately
  // after the header, BEFORE the keys.  Landed additively like
  // vals_per_key and the codec bits: the server strips it at the
  // parsing layer and (when --trace_journal is set) logs a per-handler
  // span joined to the client's span — every downstream handler sees
  // exactly the frame an untraced client would have sent.  A client may
  // set this bit ONLY after the kHello capability handshake proved
  // every server of the group parses it (kCapTrace): an un-negotiated
  // trailer against a pre-trace server would desynchronize the stream
  // (16 bytes misread as keys).  Responses never carry the trailer
  // (Respond clears the bit), and ops with no sampled trace context
  // are wire-byte-identical to the pre-trace protocol.
  kTraced = 128,
};

// Trace-context trailer of a kTraced request frame.  span_id is the
// CLIENT-side op span: the server's handler span (logged to its span
// journal) parents itself under it, which is what stitches the
// cross-process timeline together in `launch trace-agg`.
#pragma pack(push, 1)
struct TraceFrame {
  uint64_t trace_id;
  uint64_t span_id;
};
#pragma pack(pop)
static_assert(sizeof(TraceFrame) == 16, "TraceFrame must be 16 bytes");

// --- gradient wire codecs (the Flags bits 4-5 field) -------------------
//
// A coded push replaces the num_keys*vpk f32 value payload with:
//   kCodecInt8: ceil(n/kQuantBlock) f32 per-block scales, then n int8
//               quantized values (block-symmetric: scale = amax/127,
//               q = rint(v/scale) clamped to [-127, 127]) — ~3.9x
//               fewer value bytes, error bounded by scale/2 per coord;
//   kCodecSign: ceil(n/8) bytes, bit i (LSB-first) = (v_i > 0) — the
//               1-bit signSGD encoding (Bernstein et al.): decode is
//               +1/-1, with NO abstention — an exact zero decodes -1
//               and votes like any other coordinate.  Safe when the
//               gradient crossing the wire is dense in the measure-
//               theoretic sense (the paper's regime: every coordinate
//               stochastically nonzero); NOT safe for a full-width
//               push of an effectively-sparse gradient, where every
//               never-touched coordinate's -1 vote walks its weight
//               +lr per round.  Sparse workloads must push touched
//               keys only (the keyed path) or use kCodecInt8 (a zero
//               block encodes exactly); the Python client logs a
//               one-time warning when a sign-coded push is mostly
//               zeros.  Pairs with the server's signsgd majority-vote
//               optimizer; the capability mask only advertises it there.
// Keys, headers, and every reply stay dense/uncompressed — pulls are
// the serving tier's path and already have keyed/chunked/hot-row
// reductions; the PUSH payload is what crosses the wire every batch.
//
// --- values in a mapping (kCodecMapped) --------------------------------
// A client and a server on one host, connected directly, share one
// mapping a connection: a request area and a reply area, each as large
// as that server's slice of the handle's key space.  A keyed frame
// (kPush, kPushPull, kPull; init pushes too) whose codec field says
// kCodecMapped carries its header, trace trailer and keys on the socket
// as ever and NO values there: a push's values stand at the start of
// the request area, and the reply to such a frame (the field echoed)
// is its 24-byte header, num_keys saying how many values stand at the
// start of the reply area.  They are the float32 values the socket
// would have carried, bit for bit; every handler reads and writes them
// in place.  Order, the withheld reply that is the barrier, timeouts,
// poisoning, the epoch fence and a closed socket as the sign of a dead
// peer are the socket's and unchanged.
//   Handed over: a request is handed over when its values are copied
// into the request area AND its header and keys are in the kernel (the
// client's exchange instant 1); the server reads the area only after it
// has read that header.  Acknowledged: the server has done with the
// request area (merged, applied, logged) before it writes the reply
// header, except that a push withheld at the BSP barrier is read once
// more in place if its connection closes first (the rollback); the
// client writes the area again only after it has read that header (one
// operation in flight a connection), so an acknowledged push was read
// whole.  The reply's values are all in the reply area before the
// header is written, and the server writes that area again only for
// the connection's next operation.
//   Which frames: the client picks frame by frame, from the connection's
// attach, the frame's size (kMappedMinBytes of values or more: under
// it the copy saved is smaller than the mapping's cache misses cost)
// and its codec (a coded push and an opt-state operation stay on the
// socket).  Scattered keyed frames over the size ride it like runs:
// their values are as contiguous.  The server answers a frame in the
// carrier it came in.  A mapped frame on a connection that never
// attached, or one that claims more values than the area's real length
// holds, is wire corruption: the connection is dropped, not the server.
//   The attach (kHello, after the capability pass saw kCapMapped; at
// connect and at every reconnect; only for a slice of kMappedMinBytes
// or more, since no smaller frame would ever use it):
//   1. ASK: kHello, codec field kCodecMapped, aux kMappedAsk, two keys:
//      the client's own address of this socket (getsockname: IPv4 << 16
//      | port) and the values an area must hold.  The server refuses
//      (an empty reply) unless that address is its accepted socket's
//      peer (getpeername): a proxy, a NAT or a relay in between reads
//      as "not direct".  Else it makes the segment, an anonymous sealed
//      memory file (memfd: sized by the server, sealed against shrink,
//      grow and further seals, so no peer can SIGBUS the other; under
//      no name in any file system, so nothing outlives a killed
//      process), maps it, writes a random nonce at its start, and
//      answers three u64 in six Val slots: its pid, the file's
//      descriptor number, the values an area holds.
//   2. CONFIRM: the client opens /proc/<pid>/fd/<n> (one host: the same
//      file; another host: nothing, or a file that fails the checks),
//      checks it is a sealed memory file of the promised length, maps it
//      and sends kHello, aux kMappedConfirm, one key: the nonce it read
//      there (0 where it could not).  The server compares, closes its
//      descriptor either way (from here on the segment has no handle
//      but the two mappings) and answers one u64 1 in two Val slots, or
//      the empty reply and unmaps.
// Layout: kMappedHeaderBytes (u64 nonce, u64 values an area holds),
// then the request area, then the reply area, each rounded up to a
// multiple of kMappedHeaderBytes (MappedStride).  Any refusal or
// failure is a silent fall back to values on the socket, for that
// connection; one segment a connection, never handed to another.
enum Codec : uint8_t {
  kCodecNone = 0,
  kCodecInt8 = 1,
  kCodecSign = 2,
  kCodecMapped = 3,
};

//: value payloads under this many bytes stay on the socket, attached or
//: not.  From a reading on the chip's host (PR 35, call 1: a sweep of
//: sizes with this constant at 4096, PERF.md section 6; a fused
//: push-pull's median ms, socket / mapping): at 64 KiB 0.513 / 0.387 for
//: one worker and 1.209 / 1.146 for four in lock step, at 2 MiB 2.722 /
//: 1.757 and 4.423 / 3.243; from 4 to 32 KiB the mapping read 0.05-0.08
//: ms under the socket for one worker and 0.04-0.06 for four, which is
//: three system calls on that host and inside the legs' p90 spread
//: (0.07-0.45 ms).  So the mapping is never the dearer carrier there;
//: the constant stands where its gain is plain for one worker and for
//: four, and where a connection is worth a segment and two round trips
//: of attach at all.
constexpr uint64_t kMappedMinBytes = 65536;
//: the mapping's header, and the alignment of its two areas
constexpr uint64_t kMappedHeaderBytes = 4096;
//: a mapped kHello's aux: the attach's two steps
constexpr uint64_t kMappedAsk = 1;
constexpr uint64_t kMappedConfirm = 2;

// Bytes from one area's start to the next: an area of `vals` values
// rounded up to whole pages.
inline uint64_t MappedStride(uint64_t vals) {
  const uint64_t bytes = vals * sizeof(float);
  return (bytes + kMappedHeaderBytes - 1) / kMappedHeaderBytes *
         kMappedHeaderBytes;
}

// The segment's whole length for areas of `vals` values.
inline uint64_t MappedBytes(uint64_t vals) {
  return kMappedHeaderBytes + 2 * MappedStride(vals);
}

//: int8 block-quantization granularity (values per f32 scale)
constexpr uint64_t kQuantBlock = 256;

inline uint8_t CodecOf(uint8_t flags) {
  return (flags & kCodecMask) >> kCodecShift;
}

// Exact value-payload size of a coded frame carrying n values — both
// sides derive it from (codec, n), so a compressed frame needs no extra
// length field and stays as corruption-guarded as the dense layout.
inline uint64_t CodecPayloadBytes(uint8_t codec, uint64_t n) {
  if (codec == kCodecMapped) return 0;  // the values are not on the socket
  if (codec == kCodecInt8)
    return ((n + kQuantBlock - 1) / kQuantBlock) * 4 + n;
  if (codec == kCodecSign) return (n + 7) / 8;
  return n * sizeof(float);
}

// Shared by client (encode) and server (decode) so the two sides cannot
// drift: one definition of the byte layout, compiled into both.
inline void EncodeGrad(uint8_t codec, const float* v, uint64_t n,
                       uint8_t* out) {
  if (codec == kCodecInt8) {
    const uint64_t nb = (n + kQuantBlock - 1) / kQuantBlock;
    int8_t* q = reinterpret_cast<int8_t*>(out + nb * 4);
    for (uint64_t b = 0; b < nb; ++b) {
      const uint64_t lo = b * kQuantBlock;
      const uint64_t hi = lo + kQuantBlock < n ? lo + kQuantBlock : n;
      float amax = 0.0f;
      for (uint64_t i = lo; i < hi; ++i) {
        const float a = v[i] < 0 ? -v[i] : v[i];
        if (a > amax) amax = a;
      }
      const float scale = amax / 127.0f;
      std::memcpy(out + b * 4, &scale, 4);
      for (uint64_t i = lo; i < hi; ++i) {
        if (scale == 0.0f) {
          q[i] = 0;
          continue;
        }
        // nearbyintf default mode = round-half-to-even = np.rint: the
        // NumPy reference codec (distlr_tpu/compress/codecs.py) must
        // reproduce this bit for bit
        float r = nearbyintf(v[i] / scale);
        if (r > 127.0f) r = 127.0f;
        if (r < -127.0f) r = -127.0f;
        q[i] = static_cast<int8_t>(r);
      }
    }
  } else if (codec == kCodecSign) {
    const uint64_t nb = (n + 7) / 8;
    for (uint64_t b = 0; b < nb; ++b) out[b] = 0;
    for (uint64_t i = 0; i < n; ++i) {
      if (v[i] > 0.0f) out[i / 8] |= static_cast<uint8_t>(1u << (i % 8));
    }
  }
}

inline void DecodeGrad(uint8_t codec, const uint8_t* in, uint64_t n,
                       float* out) {
  if (codec == kCodecInt8) {
    const uint64_t nb = (n + kQuantBlock - 1) / kQuantBlock;
    const int8_t* q = reinterpret_cast<const int8_t*>(in + nb * 4);
    for (uint64_t i = 0; i < n; ++i) {
      float scale;
      std::memcpy(&scale, in + (i / kQuantBlock) * 4, 4);
      out[i] = static_cast<float>(q[i]) * scale;
    }
  } else if (codec == kCodecSign) {
    for (uint64_t i = 0; i < n; ++i) {
      out[i] = (in[i / 8] >> (i % 8)) & 1 ? 1.0f : -1.0f;
    }
  }
}

// --- kHello capability handshake ---------------------------------------
// A capability-aware server answers kHello with ONE f64 bitmask shipped
// as 2 Val slots (the kStats float64-in-Val convention); a legacy
// server echoes an EMPTY reply (num_keys == 0), which the client reads
// as "no capabilities" and falls back to dense f32 — negotiation is
// additive, no version field needed.  kCapCodecSign is advertised only
// by --optimizer=signsgd servers: decoded ±1 votes through any other
// update rule would be sign-mean, not the paper's majority vote.
constexpr uint64_t kCapCodecInt8 = 1ull << kCodecInt8;
constexpr uint64_t kCapCodecSign = 1ull << kCodecSign;
// The server parses kTraced frames (the 16-byte TraceFrame trailer).
// Advertised by every capability-aware server; a kHello request that
// itself sets kTraced additionally asks for the server's wall clock in
// the reply (4 Val slots: [caps f64, unix-seconds f64]) — the clock-
// skew probe `launch trace-agg` aligns cross-host span timelines with.
// Plain kHello requests keep the 2-slot reply, so pre-trace clients
// never see a frame shape they cannot parse.
constexpr uint64_t kCapTrace = 1ull << 8;
// The server speaks the kEpoch membership op (announce/query/set) and
// fences announced connections on epoch mismatch — the elastic-fleet
// capability.  A client must see this from EVERY server before
// announcing an epoch: a kEpoch frame against a pre-epoch binary would
// never be answered (unknown ops are skipped, not nacked).
constexpr uint64_t kCapEpoch = 1ull << 9;
// The server shares a mapping with a same-host client that asks
// (kCodecMapped, "values in a mapping" above).  A client asks only
// after it saw this bit: an older server would answer an ASK with its
// capability mask.
constexpr uint64_t kCapMapped = 1ull << 10;

#pragma pack(push, 1)
struct MsgHeader {
  uint32_t magic;
  uint8_t op;
  uint8_t flags;
  // Op-specific 16-bit field:
  //   kBarrier — the barrier GENERATION id.  Barriers are counted per
  //   id, and an id that has already released replies instantly to
  //   late votes — so a restarted worker re-voting the startup barrier
  //   (id 0) can never pair with peers' exit-barrier votes (id 1), and
  //   never hangs regardless of when its predecessor crashed.
  //   kPush/kPull/kPushPull — vals_per_key (0 == 1 == scalar keys); see
  //   the frame-layout comment above.
  uint16_t aux;
  uint32_t client_id;
  uint32_t timestamp;   // per-client op sequence number (ps-lite ts)
  uint64_t num_keys;
};
#pragma pack(pop)

// Wire-corruption guard for vals_per_key: large enough for any
// realistic row width (the blocked path uses R in {8, 16, 32}), small
// enough to reject essentially all random u16s.
constexpr uint64_t kMaxValsPerKey = 4096;

static_assert(sizeof(MsgHeader) == 24, "MsgHeader must be 24 bytes");

// --- durable store: on-DISK formats (--store_dir) ----------------------
//
// Disk formats are protocol too: the Python reader (distlr_tpu/ps/
// store.py) mirrors every constant here, and the analysis wire-parity
// pass fails `make lint` on any drift — the same lint culture that
// pins the socket framing above.
//
// Snapshot file (snap-0.bin / snap-1.bin, two alternating generations;
// written tmp+fsync+rename so a reader never sees a half-written
// generation — torn files can only come from a crash mid-rename-free
// filesystem, and the CRC rejects them):
//   40-byte header, little-endian, no padding:
//     u32 magic         kStoreMagic
//     u16 version       kStoreVersion (bump on ANY layout change)
//     u16 flags         kStoreFlagFtrl | kStoreFlagInitialized
//     u16 epoch         membership epoch at capture (kEpoch round)
//     u16 reserved      zero
//     u32 crc           CRC32 (zlib polynomial) over the header with
//                       this field zeroed, then the whole payload
//     u64 dim           weights_.size() at capture
//     u64 push_clock    n_push_ at capture — the RPO audit clock
//     f64 wall_time_s   capture wall time (snapshot-age metric)
//   payload: dim f32 weights, then (flags & kStoreFlagFtrl) dim f32 z
//   and dim f32 n — the FTRL accumulators, so a restore is never a
//   silent warm restart.
//
// WAL segment (wal-<push_clock>.log, append-only, rotated at every
// snapshot; a segment named wal-C holds exactly the records with
// seq > C up to the next rotation's clock — which is what makes
// "delete segments older than the oldest on-disk generation" safe):
//   8-byte segment header: u32 kWalMagic, u16 kStoreVersion, u16 epoch
//   then records, each:
//     20-byte record header: u64 seq (n_push_ AFTER the mutation; the
//       replay skip/apply cursor), u32 nkeys, u8 flags (the wire Flags
//       bits that describe the mutation: kInitPush/kForceInit/
//       kOptState), u8 op (Op::kPush, or Op::kEpoch for a membership
//       flip — then reserved carries the new epoch and nkeys == 0),
//       u16 reserved, u32 crc (CRC32 over the record payload)
//     payload: nkeys u64 keys, then nvals f32 vals where nvals is
//       2*nkeys for kOptState records (the [z..., n...] layout) and
//       nkeys otherwise.
//   A torn tail (crash mid-append) truncates replay at the first short
//   or CRC-failing record — loudly, never silently.
constexpr uint32_t kStoreMagic = 0xD157510D;
constexpr uint32_t kStoreVersion = 1;
constexpr uint32_t kStoreHeaderSize = 40;
//: generations kept on disk (alternating snap-0 / snap-1)
constexpr uint32_t kStoreGenerations = 2;
//: snapshot header flag bits
constexpr uint32_t kStoreFlagFtrl = 1;
constexpr uint32_t kStoreFlagInitialized = 2;
constexpr uint32_t kWalMagic = 0xD157106D;
constexpr uint32_t kWalHeaderSize = 8;
constexpr uint32_t kWalRecordHeaderSize = 20;

using Key = uint64_t;
using Val = float;

// One side's view of a connection's segment ("values in a mapping"
// above).  area_vals is what the mapping's real length holds: every
// frame's size is checked against it, never against a count a peer sent.
struct MappedSegment {
  uint8_t* base = nullptr;
  uint64_t bytes = 0;
  uint64_t area_vals = 0;
  // both sides hold the mapping and the nonce was read back: frames may
  // say kCodecMapped
  bool attached = false;

  Val* req() const {
    return reinterpret_cast<Val*>(base + kMappedHeaderBytes);
  }
  Val* reply() const {
    return reinterpret_cast<Val*>(base + kMappedHeaderBytes +
                                  MappedStride(area_vals));
  }
  // Map the whole of memory file `fd` (MappedBytes(vals) long; the
  // caller has checked or made that length).
  bool Map(int fd, uint64_t vals) {
    void* p = mmap(nullptr, MappedBytes(vals), PROT_READ | PROT_WRITE,
                   MAP_SHARED, fd, 0);
    if (p == MAP_FAILED) return false;
    base = static_cast<uint8_t*>(p);
    bytes = MappedBytes(vals);
    area_vals = vals;
    return true;
  }
  void Unmap() {
    if (base != nullptr) munmap(base, bytes);
    *this = MappedSegment{};
  }
};

}  // namespace distlr

#endif  // DISTLR_TPU_PS_KV_PROTOCOL_H_
