// Core runtime / background thread — TPU-native equivalent of
// horovod/common/operations.{h,cc} (N3), exposed as a C API for ctypes.
//
// Architecture: the reference's background thread owns negotiation, tensor
// fusion and the MPI/NCCL calls (operations.cc:1695-1999, 2030-2380). On
// TPU the data plane is XLA — collectives execute as jitted programs
// launched from Python — so the native runtime keeps everything *around*
// the collective: the tensor table with duplicate-name rejection
// (operations.cc:270-273, 2472-2509), the cycle timer, negotiation via
// MessageTable + ConstructResponse, fusion planning with look-ahead
// (operations.cc:2149-2265), the timeline, stall detection, and the
// autotuner. Execution requests flow to Python through a registered
// callback (the role the PerformOperation dispatch plays in the reference);
// Python reports completion back so the runtime can close timeline events,
// clear in-flight names, and feed the autotuner.
//
// Threading: one background thread per process (operations.cc:109-114); a
// single mutex guards queue+table (operations.cc:120-127); the execute
// callback is invoked WITHOUT the lock held (it re-enters Python, which
// takes the GIL).

#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstring>
#include <deque>
#include <mutex>
#include <thread>
#include <unordered_map>

#include <algorithm>
#include <cstdio>
#include <cstdlib>

#include "common.h"
#include "coordinator.h"
#include "half.h"
#include "fusion_buffer.h"
#include "logging.h"
#include "message.h"
#include "parameter_manager.h"
#include "timeline.h"

namespace hvdtpu {
namespace {

using Clock = std::chrono::steady_clock;

typedef void (*ExecuteCallback)(void* user, int32_t op,
                                const int64_t* handles, int32_t count,
                                const char* error_message);

// Multi-process transport bridge (the MPI_Gatherv/Bcast legs of the
// reference cycle, operations.cc:2324-2345, carried by Python over the
// launcher's TCP control plane). The background thread hands Python this
// process's serialized RequestList; Python announces it to the rank-0
// controller and long-polls the agreed ResponseList, whose bytes it
// writes into resp_buf. Returns bytes written, 0 for "nothing yet", or
// -(needed) when resp_cap is too small (the cycle retries with a larger
// buffer).
// `complete` is 1 when the drained batch is a COMPLETE enqueue burst
// (drained after debounce-quiet or an explicit flush hint, not by the
// max-defer valve) — the coordinator may plan eagerly the moment every
// rank's complete announce has landed, skipping its own quiet window.
typedef int64_t (*TransportCallback)(void* user, const uint8_t* req_bytes,
                                     int64_t req_len, int32_t nreq,
                                     int32_t complete, int64_t pending,
                                     uint8_t* resp_buf, int64_t resp_cap);

// Delivery of one coordinator-agreed group to Python for XLA execution
// (the PerformOperation dispatch, operations.cc:768-791). `nnames` is the
// group's tensor count as planned; `count` the handles found locally —
// a mismatch means local/coordinator desync, which Python treats as fatal
// rather than skipping a collective its peers will enter. `sizes` carries
// the per-rank first dims for allgather (nnames * nproc entries in
// tensor_names order); `flags` the plan-time execution-mode bits.
typedef void (*GroupCallback)(void* user, int32_t op, const int64_t* handles,
                              int32_t count, int32_t nnames,
                              const int64_t* sizes, int32_t nsizes,
                              int32_t flags, const char* error_message);

struct PendingEntry {
  int64_t handle;
  Request request;
  int64_t nbytes;
  Clock::time_point enqueued;
  bool executing = false;  // negotiated & handed to the execute callback
};

struct HandleState {
  std::string name;
  int32_t status = -1;  // -1 in flight; else StatusType
  std::string reason;
};

struct GlobalState {
  std::mutex mu;
  std::atomic<bool> initialized{false};
  std::atomic<bool> shutdown_requested{false};
  bool background_done = false;
  std::condition_variable shutdown_cv;

  int rank = 0, size = 1, local_size = 1, virtual_size = 1;

  std::thread background;

  // Message queue + tensor table (operations.cc:120-143).
  std::deque<PendingEntry> message_queue;
  std::unordered_map<std::string, PendingEntry> tensor_table;  // in flight
  std::unordered_map<int64_t, HandleState> handles;
  int64_t next_handle = 1;

  MessageTable message_table;

  ExecuteCallback execute_cb = nullptr;
  void* execute_user = nullptr;
  TransportCallback transport_cb = nullptr;
  void* transport_user = nullptr;
  GroupCallback group_cb = nullptr;
  void* group_user = nullptr;

  // Knobs (operations.cc:1824-1909).
  std::atomic<int64_t> fusion_threshold{64LL * 1024 * 1024};
  std::atomic<int64_t> cycle_time_us{1000};
  double stall_warning_sec = 60.0;  // STALL_WARNING_TIME operations.cc:258
  Clock::time_point last_stall_check = Clock::now();

  Timeline timeline;
  FusionBufferManager fusion_buffers;
  ParameterManager param_manager;

  // Cycle stats for the autotuner.
  std::atomic<int64_t> cycle_bytes{0};

  // Enqueue-burst debounce: steady_clock nanos of the newest and oldest
  // queued request. A cycle defers draining while a burst is still
  // arriving (< kDrainDebounceNs since the last enqueue) so one training
  // step's requests always fuse into the same groups — every distinct
  // group composition is a distinct fused XLA program, and timing-
  // dependent chunking would mean a fresh compile per step instead of a
  // cache hit. kDrainMaxDeferNs bounds the wait so a continuous enqueue
  // stream cannot starve dispatch, and a queue that did not GROW since
  // the previous check drains immediately — a lone blocking caller's
  // single request must not pay the debounce (its submitter is stuck on
  // the handle; no burst can follow).
  std::atomic<int64_t> last_enqueue_ns{0};
  std::atomic<int64_t> oldest_enqueue_ns{0};
  size_t last_seen_qlen = 0;  // background thread only

  // Flush hint (hvdtpu_flush): a submitter about to block on a handle
  // declares its burst fully enqueued — the cycle drains NOW instead of
  // waiting out the debounce, and the cycle's pacing sleep is interrupted
  // via cycle_cv so the drain starts immediately.
  std::atomic<bool> flush_hint{false};
  // Explicit burst scope (hvdtpu_burst_begin/end): while a submitter has
  // a burst open, the drain defers REGARDLESS of queue growth. The
  // growth heuristic alone misfires on an oversubscribed host: the
  // enqueueing thread gets descheduled mid-burst for > the debounce
  // window, the cycle sees "stopped growing" and drains a PARTIAL burst
  // — a new fusion composition, hence a fresh XLA compile, every step.
  std::atomic<int32_t> burst_depth{0};
  // Burst-scope owner threads (per-thread open-scope count). A flush
  // hint from a thread that owns NO open scope — a foreign waiter
  // blocking on a handle while another thread's scope is open — must
  // cut the scope instead of being consumed, or the waiter stalls until
  // the 1 s burst valve fires (a per-op latency landmine).
  std::mutex burst_owner_mu;
  std::unordered_map<std::thread::id, int32_t> burst_owners;
  std::atomic<bool> foreign_flush{false};
  std::condition_variable cycle_cv;
  std::mutex cycle_mu;
};

constexpr int64_t kDrainDebounceNs = 2'000'000;    // 2 ms
constexpr int64_t kDrainMaxDeferNs = 20'000'000;   // 20 ms
// Explicit burst scopes get a much larger valve: the submitter's
// burst_end IS the drain boundary, and on an oversubscribed host a
// 50-leaf enqueue loop alone can take > 20 ms of wall time. Cutting it
// mid-scope makes the group composition (and the quantized fusion-buffer
// sizes) timing-dependent — a fresh XLA compile per step. The valve only
// guards against a submitter that hangs inside an open scope.
constexpr int64_t kBurstMaxDeferNs = 1'000'000'000;  // 1 s

int64_t NowNs() {
  return std::chrono::duration_cast<std::chrono::nanoseconds>(
             Clock::now().time_since_epoch())
      .count();
}

// True while an enqueue burst is still arriving (defer the drain). When
// returning false (drain now), *complete reports whether the drained
// batch is a COMPLETE burst: true for debounce-quiet / flush-hint /
// stopped-growing drains, false only for the max-defer valve (the burst
// may still be arriving).
bool DrainShouldDefer(GlobalState& st, bool* complete) {
  *complete = true;
  if (st.shutdown_requested.load()) return false;  // drain for teardown
  std::lock_guard<std::mutex> lk(st.mu);
  size_t qlen = st.message_queue.size();
  size_t last = st.last_seen_qlen;
  st.last_seen_qlen = qlen;
  if (st.burst_depth.load() > 0 && qlen > 0) {
    // Submitter declared a burst open: defer regardless of growth (the
    // growth heuristic misfires when the enqueuer is descheduled on a
    // busy host), bounded by the burst valve. The scope OWNER's flush
    // hint is consumed here — the open scope supersedes it (its own
    // burst_end will flush), and leaving it set would defeat
    // CycleSleep's pacing for the rest of the scope (a hot spin). A
    // FOREIGN waiter's hint (a thread with no open scope blocking on a
    // handle, hvdtpu_flush) cuts the scope instead: stalling that
    // waiter until the 1 s valve is a worse failure mode than one
    // timing-dependent group composition.
    st.flush_hint.store(false);
    if (st.foreign_flush.exchange(false)) {
      *complete = false;  // mid-scope cut: the burst may still be arriving
      return false;
    }
    if (NowNs() - st.oldest_enqueue_ns.load() >= kBurstMaxDeferNs) {
      *complete = false;
      return false;
    }
    return true;
  }
  // No open scope. Clear a foreign mark ONLY together with consuming
  // its paired flush hint — hvdtpu_flush stores foreign_flush first,
  // then flush_hint, and a cycle landing between the two stores must
  // not wipe the mark (the waiter hints only once; losing the mark and
  // then having a scope open re-creates the 1 s stall). A mark whose
  // hint has not landed yet survives to the next cycle.
  if (st.flush_hint.exchange(false)) {
    st.foreign_flush.store(false);
    return false;  // submitter says done
  }
  if (qlen == 0) return false;
  if (qlen <= last) return false;  // burst stopped growing: drain now
  int64_t now = NowNs();
  if (now - st.oldest_enqueue_ns.load() >= kDrainMaxDeferNs) {
    *complete = false;
    return false;
  }
  return now - st.last_enqueue_ns.load() < kDrainDebounceNs;
}

// Pace out the remainder of the cycle, interruptibly: a flush hint or
// shutdown wakes the sleep so a known-complete burst drains immediately
// instead of waiting out the cycle timer.
void CycleSleep(GlobalState& st, Clock::time_point cycle_start) {
  auto elapsed = Clock::now() - cycle_start;
  auto cycle = std::chrono::microseconds(st.cycle_time_us.load());
  if (elapsed >= cycle) return;
  std::unique_lock<std::mutex> lk(st.cycle_mu);
  st.cycle_cv.wait_for(lk, cycle - elapsed, [&] {
    return st.flush_hint.load() || st.shutdown_requested.load();
  });
}

GlobalState* g_state = nullptr;

void EmitTimelineStartGroup(GlobalState& st, const Response& resp) {
  static const char* kOpName[] = {"ALLREDUCE", "ALLGATHER", "BROADCAST"};
  if (!st.timeline.Initialized()) return;
  for (const auto& name : resp.tensor_names) {
    st.timeline.NegotiateEnd(name);
    if (resp.response_type != Response::ERROR) {
      st.timeline.Start(name, kOpName[resp.response_type]);
      st.timeline.ActivityStart(name, "QUEUE");
    }
  }
}

// Deliver the coordinator's agreed groups to Python (multi-process mode).
// Mirrors the worker half of the reference cycle after the response Bcast
// (operations.cc:2361-2377): every process executes the SAME groups in the
// SAME order — here as jitted SPMD programs launched by the group callback.
void HandleResponsesMP(GlobalState& st, ResponseList& list) {
  GroupCallback cb;
  void* user;
  {
    std::lock_guard<std::mutex> lk(st.mu);
    cb = st.group_cb;
    user = st.group_user;
  }
  if (list.shutdown) {
    // A peer announced shutdown — possibly from its teardown path, in
    // which case it will never enter the SPMD programs for the groups
    // delivered alongside the flag. Executing them could hang this rank
    // in an XLA collective, so fail EVERYTHING not yet executing with
    // SHUT_DOWN_ERROR (matching the reference's drain of queued tensors,
    // operations.cc:1942-1998, and the Python fallback's behavior —
    // mixed fleets must make the same call or they deadlock each other).
    std::vector<int64_t> hs;
    {
      std::lock_guard<std::mutex> lk(st.mu);
      for (const auto& kv : st.tensor_table)
        if (!kv.second.executing) hs.push_back(kv.second.handle);
      st.message_queue.clear();
    }
    if (!hs.empty() && cb)
      cb(user, static_cast<int32_t>(Response::ERROR), hs.data(),
         static_cast<int32_t>(hs.size()), static_cast<int32_t>(hs.size()),
         nullptr, 0, 0,
         "Horovod has been shut down. This was caused by an exception on "
         "one of the ranks or an attempt to run a collective after one of "
         "the ranks finished execution.");
    st.shutdown_requested.store(true);
    return;
  }
  for (auto& resp : list.responses) {
    EmitTimelineStartGroup(st, resp);
    std::vector<int64_t> hs;
    {
      std::lock_guard<std::mutex> lk(st.mu);
      for (const auto& name : resp.tensor_names) {
        auto it = st.tensor_table.find(name);
        if (it != st.tensor_table.end()) {
          it->second.executing = true;
          hs.push_back(it->second.handle);
        }
      }
    }
    if (cb)
      cb(user, static_cast<int32_t>(resp.response_type), hs.data(),
         static_cast<int32_t>(hs.size()),
         static_cast<int32_t>(resp.tensor_names.size()),
         resp.tensor_sizes.data(),
         static_cast<int32_t>(resp.tensor_sizes.size()), resp.flags,
         resp.error_message.c_str());
  }
}

// Multi-process cycle: serialize the drained batch, hand it to the Python
// transport (announce + long-poll fetch over TCP), parse the agreed
// ResponseList, dispatch groups. The reference's RunLoopOnce worker half
// (operations.cc:2323-2377) with message.cc's codec as the wire format.
bool RunLoopOnceMP(GlobalState& st) {
  auto cycle_start = Clock::now();
  st.timeline.MarkCycleStart();

  // Burst debounce, as in RunLoopOnce: announcing a partial burst would
  // chunk the coordinator's view and destabilize fusion groups. While
  // deferring, skip the transport leg entirely — its fetch long-poll
  // would hold the rest of the burst back for up to 50 ms.
  bool complete = true;
  if (DrainShouldDefer(st, &complete)) {
    CycleSleep(st, cycle_start);
    return true;  // next cycle drains (defer is max-defer bounded)
  }
  std::deque<PendingEntry> batch;
  {
    std::lock_guard<std::mutex> lk(st.mu);
    batch = std::move(st.message_queue);
    st.message_queue.clear();
    st.last_seen_qlen = 0;
  }
  RequestList rl;
  for (auto& pe : batch) rl.requests.push_back(pe.request);

  int64_t pending;
  TransportCallback cb;
  void* user;
  {
    std::lock_guard<std::mutex> lk(st.mu);
    pending = static_cast<int64_t>(st.tensor_table.size());
    cb = st.transport_cb;
    user = st.transport_user;
  }

  if (cb && (!rl.requests.empty() || pending > 0)) {
    std::vector<uint8_t> req_buf;
    rl.SerializeTo(&req_buf);
    static thread_local std::vector<uint8_t> resp_buf(1 << 20);
    int64_t n = cb(user, req_buf.data(),
                   static_cast<int64_t>(req_buf.size()),
                   static_cast<int32_t>(rl.requests.size()),
                   complete ? 1 : 0, pending,
                   resp_buf.data(), static_cast<int64_t>(resp_buf.size()));
    if (n < 0) {
      resp_buf.resize(static_cast<size_t>(-n));
      n = cb(user, req_buf.data(), static_cast<int64_t>(req_buf.size()),
             0 /*already announced*/, complete ? 1 : 0, pending,
             resp_buf.data(), static_cast<int64_t>(resp_buf.size()));
    }
    if (n > 0) {
      ResponseList list;
      if (ResponseList::ParseFrom(resp_buf.data(), static_cast<size_t>(n),
                                  &list)) {
        HandleResponsesMP(st, list);
      } else {
        HVD_LOG(WARNING) << "could not parse coordinator response list ("
                         << n << " bytes); skipping cycle";
      }
    }
  }

  // Local stall hint (names only): the coordinator's fetch responses carry
  // the authoritative missing-ranks report (hvdtpu_ctl_stalled), which
  // Python logs on every process.
  if (st.stall_warning_sec > 0) {
    auto now = Clock::now();
    if (std::chrono::duration<double>(now - st.last_stall_check).count() >
        st.stall_warning_sec) {
      st.last_stall_check = now;
      std::vector<std::string> stalled;
      {
        std::lock_guard<std::mutex> lk(st.mu);
        for (const auto& kv : st.tensor_table)
          if (!kv.second.executing) {
            double age =
                std::chrono::duration<double>(now - kv.second.enqueued)
                    .count();
            if (age > st.stall_warning_sec) stalled.push_back(kv.first);
          }
      }
      if (!stalled.empty()) {
        std::string names;
        for (const auto& n : stalled)
          names += (names.empty() ? "" : ", ") + n;
        HVD_LOG(WARNING)
            << "One or more tensors were submitted to be reduced, gathered "
            << "or broadcasted by subset of ranks and are waiting for "
            << "remainder of ranks for more than " << st.stall_warning_sec
            << " seconds. Stalled ops: " << names;
      }
    }
  }

  if (st.shutdown_requested.load()) {
    std::lock_guard<std::mutex> lk(st.mu);
    if (st.message_queue.empty()) return false;
  }

  CycleSleep(st, cycle_start);
  return true;
}

// One cycle of the background loop (RunLoopOnce, operations.cc:2030-2380).
// Returns false when shutdown was requested and the queue is drained.
bool RunLoopOnce(GlobalState& st) {
  auto cycle_start = Clock::now();
  st.timeline.MarkCycleStart();

  // Drain local queue under lock (operations.cc:2050-2058) — unless an
  // enqueue burst is still arriving (DrainShouldDefer): draining
  // mid-burst would cut timing-dependent fusion groups and recompile
  // their XLA programs every step.
  std::deque<PendingEntry> batch;
  bool complete = true;
  if (!DrainShouldDefer(st, &complete)) {
    std::lock_guard<std::mutex> lk(st.mu);
    batch = std::move(st.message_queue);
    st.message_queue.clear();
    st.last_seen_qlen = 0;
  }

  // Negotiation: every enqueue on the single-controller path announces the
  // tensor for ALL local virtual ranks at once, so readiness counting runs
  // at process granularity. With one process (size_procs == 1) tensors are
  // ready immediately; the multi-host controller feeds remote request
  // lists into the same MessageTable.
  std::deque<Response> ready;
  std::unordered_map<std::string, int64_t> sizes;
  std::unordered_map<std::string, DataType> dtypes;
  std::unordered_map<std::string, std::vector<int64_t>> handle_of;
  {
    std::lock_guard<std::mutex> lk(st.mu);
    for (auto& pe : batch) {
      bool all_ready = st.message_table.Increment(pe.request, /*size=*/1);
      sizes[pe.request.tensor_name] = pe.nbytes;
      dtypes[pe.request.tensor_name] = pe.request.tensor_type;
      handle_of[pe.request.tensor_name].push_back(pe.handle);
      if (all_ready) {
        auto reqs = st.message_table.Take(pe.request.tensor_name);
        ready.push_back(ConstructResponse(reqs, 1, st.virtual_size));
      }
    }
  }

  if (!ready.empty()) {
    // Fusion planning with look-ahead (operations.cc:2149-2265).
    auto plans = FuseResponses(std::move(ready), sizes, dtypes,
                               st.fusion_threshold.load());

    for (auto& resp : plans) {
      EmitTimelineStartGroup(st, resp);
      std::vector<int64_t> hs;
      int64_t bytes = 0;
      {
        std::lock_guard<std::mutex> lk(st.mu);
        for (const auto& name : resp.tensor_names) {
          auto it = st.tensor_table.find(name);
          if (it != st.tensor_table.end()) it->second.executing = true;
        }
      }
      for (const auto& name : resp.tensor_names) {
        for (int64_t h : handle_of[name]) hs.push_back(h);
        bytes += sizes.count(name) ? sizes[name] : 0;
      }
      st.cycle_bytes.fetch_add(bytes);
      ExecuteCallback cb;
      void* user;
      {
        std::lock_guard<std::mutex> lk(st.mu);
        cb = st.execute_cb;
        user = st.execute_user;
      }
      if (resp.response_type == Response::ERROR) {
        // Mismatch verdicts are delivered to the callback as errors so the
        // owner can fail the handles (operations.cc:1613-1620 semantics).
        if (cb) cb(user, static_cast<int32_t>(resp.response_type), hs.data(),
                   static_cast<int32_t>(hs.size()),
                   resp.error_message.c_str());
      } else if (cb) {
        cb(user, static_cast<int32_t>(resp.response_type), hs.data(),
           static_cast<int32_t>(hs.size()), "");
      }
    }
  }

  // Stall detection (CheckForStalledTensors, operations.cc:1625-1672).
  if (st.stall_warning_sec > 0) {
    auto now = Clock::now();
    if (std::chrono::duration<double>(now - st.last_stall_check).count() >
        st.stall_warning_sec) {
      st.last_stall_check = now;
      std::vector<std::string> stalled;
      {
        std::lock_guard<std::mutex> lk(st.mu);
        for (const auto& kv : st.tensor_table) {
          // Only un-negotiated tensors count — the reference scans its
          // MessageTable, not ops already executing
          // (CheckForStalledTensors, operations.cc:1625-1672).
          if (kv.second.executing) continue;
          double age = std::chrono::duration<double>(now - kv.second.enqueued)
                           .count();
          if (age > st.stall_warning_sec) stalled.push_back(kv.first);
        }
      }
      if (!stalled.empty()) {
        std::string names;
        for (const auto& n : stalled) names += (names.empty() ? "" : ", ") + n;
        HVD_LOG(WARNING)
            << "One or more tensors were submitted to be reduced, gathered "
            << "or broadcasted by subset of ranks and are waiting for "
            << "remainder of ranks for more than " << st.stall_warning_sec
            << " seconds. Stalled ops: " << names;
      }
    }
  }

  if (st.shutdown_requested.load()) {
    std::lock_guard<std::mutex> lk(st.mu);
    if (st.message_queue.empty()) return false;
  }

  // Sleep out the remainder of the cycle (operations.cc:2032-2040),
  // interruptibly (flush hint / shutdown).
  CycleSleep(st, cycle_start);

  // Autotuner: feed the FULL cycle wall time including the pacing sleep —
  // the reference scores bytes over the whole interval between samples
  // (parameter_manager.cc:144-170), which is what makes the cycle-time
  // knob observable to the optimizer.
  double secs =
      std::chrono::duration<double>(Clock::now() - cycle_start).count();
  if (st.param_manager.IsAutoTuning()) {
    if (st.param_manager.Update(st.cycle_bytes.exchange(0), secs)) {
      st.fusion_threshold.store(st.param_manager.TensorFusionThresholdBytes());
      st.cycle_time_us.store(
          static_cast<int64_t>(st.param_manager.CycleTimeMs() * 1000));
    }
  } else {
    st.cycle_bytes.store(0);
  }
  return true;
}

void BackgroundThreadLoop(GlobalState& st) {
  // (BackgroundThreadLoop, operations.cc:1695-1999 — minus MPI bring-up,
  // which jax.distributed handles before this thread starts.) With more
  // than one host process, the cycle negotiates through the rank-0
  // controller over the Python transport instead of planning locally.
  const bool mp = st.size > 1;
  while (mp ? RunLoopOnceMP(st) : RunLoopOnce(st)) {
  }
  {
    std::lock_guard<std::mutex> lk(st.mu);
    st.background_done = true;
  }
  st.shutdown_cv.notify_all();
}

}  // namespace
}  // namespace hvdtpu

// ---------------------------------------------------------------------------
// C API (ctypes surface) — parity with the reference's C init/rank API
// (operations.cc:2413-2468) plus the enqueue/callback bridge.
// ---------------------------------------------------------------------------

using namespace hvdtpu;

extern "C" {

namespace {

// Serializes init/shutdown transitions; never taken by the background
// thread, so joining under it cannot deadlock.
std::mutex g_init_mu;

const char* EnvOr(const char* primary, const char* fallback) {
  const char* v = std::getenv(primary);
  return v ? v : std::getenv(fallback);
}

// Knob parsing (operations.cc:1824-1909) — shared by fresh init and
// re-init after shutdown so env-derived config (timeline, autotune,
// fusion/cycle knobs, stall check) is honored on every bring-up. Every
// knob is reset to its default first so a re-init with a *changed*
// environment behaves exactly like a fresh init (no feature stays on
// because a previous session enabled it).
void ConfigureFromEnv(GlobalState& st) {
  st.fusion_threshold.store(64LL * 1024 * 1024);  // operations.cc:1838
  st.cycle_time_us.store(1000);  // TPU default 1 ms, see utils/env.py
  st.param_manager.SetAutoTuning(false);
  const char* v = EnvOr("HOROVOD_TPU_FUSION_THRESHOLD",
                        "HOROVOD_FUSION_THRESHOLD");
  if (v) st.fusion_threshold.store(std::atoll(v));
  v = EnvOr("HOROVOD_TPU_CYCLE_TIME", "HOROVOD_CYCLE_TIME");
  if (v) st.cycle_time_us.store(static_cast<int64_t>(std::atof(v) * 1000));
  v = EnvOr("HOROVOD_TPU_STALL_CHECK_DISABLE",
            "HOROVOD_STALL_CHECK_DISABLE");
  st.stall_warning_sec = (v && std::strcmp(v, "0") != 0) ? 0 : 60;

  v = EnvOr("HOROVOD_TPU_TIMELINE", "HOROVOD_TIMELINE");
  if (v && *v && st.rank == 0) {
    const char* mc = EnvOr("HOROVOD_TPU_TIMELINE_MARK_CYCLES",
                           "HOROVOD_TIMELINE_MARK_CYCLES");
    st.timeline.Initialize(v, mc && std::strcmp(mc, "0") != 0);
  }

  v = EnvOr("HOROVOD_TPU_AUTOTUNE", "HOROVOD_AUTOTUNE");
  if (v && std::strcmp(v, "0") != 0) {
    const char* lg = EnvOr("HOROVOD_TPU_AUTOTUNE_LOG",
                           "HOROVOD_AUTOTUNE_LOG");
    st.param_manager.Initialize(st.rank, lg ? lg : "");
    st.param_manager.SetCurrent(
        st.fusion_threshold.load() / (1024.0 * 1024.0),
        st.cycle_time_us.load() / 1000.0);
    st.param_manager.SetAutoTuning(true);
  }
}

}  // namespace

int hvdtpu_init(int rank, int size, int local_size, int virtual_size) {
  // InitializeHorovodOnce (operations.cc:2384-2402). `rank`/`size` are
  // host-process granular (the negotiation unit); `virtual_size` is the
  // total device count, bounding broadcast root ranks.
  std::lock_guard<std::mutex> init_lk(g_init_mu);
  if (g_state && g_state->initialized.load()) return 0;
  if (g_state) {
    // Re-init after shutdown (test hook): reset the retained state and
    // reconfigure from the environment exactly like a fresh init.
    GlobalState& st = *g_state;
    if (st.background.joinable()) st.background.join();
    {
      std::lock_guard<std::mutex> lk(st.mu);
      st.message_queue.clear();
      st.tensor_table.clear();
      st.handles.clear();
      st.shutdown_requested.store(false);
      st.background_done = false;
      st.flush_hint.store(false);
      st.burst_depth.store(0);
      st.foreign_flush.store(false);
      {
        std::lock_guard<std::mutex> olk(st.burst_owner_mu);
        st.burst_owners.clear();
      }
      st.rank = rank;
      st.size = size;
      st.local_size = local_size;
      st.virtual_size = virtual_size > 0 ? virtual_size
                                         : size * local_size;
    }
    ConfigureFromEnv(st);
    st.background = std::thread(BackgroundThreadLoop, std::ref(st));
    st.initialized.store(true);
    return 0;
  }
  auto* st = new GlobalState();
  st->rank = rank;
  st->size = size;
  st->local_size = local_size;
  st->virtual_size = virtual_size > 0 ? virtual_size : size * local_size;
  ConfigureFromEnv(*st);
  st->background = std::thread(BackgroundThreadLoop, std::ref(*st));
  st->initialized.store(true);
  g_state = st;
  HVD_LOG(DEBUG) << "hvdtpu core initialized (rank " << rank << "/" << size
                 << ")";
  return 0;
}

int hvdtpu_initialized() {
  return g_state && g_state->initialized.load() ? 1 : 0;
}

void hvdtpu_shutdown() {
  // Coordinated shutdown (operations.cc:1942-1998): drain, stop thread,
  // close the timeline. The GlobalState is intentionally NEVER freed —
  // other threads may be concurrently inside C-API calls that already
  // passed the g_state null-check (the reference keeps its global state
  // for the process lifetime for the same reason).
  std::lock_guard<std::mutex> init_lk(g_init_mu);
  if (!g_state) return;
  GlobalState& st = *g_state;
  st.shutdown_requested.store(true);
  {
    std::lock_guard<std::mutex> lk(st.cycle_mu);  // see hvdtpu_flush
  }
  st.cycle_cv.notify_all();  // interrupt the pacing sleep
  if (st.background.joinable()) st.background.join();
  st.timeline.Shutdown();
  {
    // Python drops its trampoline references after shutdown; a stale
    // pointer surviving into a re-init would be a use-after-free.
    std::lock_guard<std::mutex> lk(st.mu);
    st.execute_cb = nullptr;
    st.transport_cb = nullptr;
    st.group_cb = nullptr;
  }
  st.initialized.store(false);
}

void hvdtpu_set_execute_callback(void (*cb)(void*, int32_t, const int64_t*,
                                            int32_t, const char*),
                                 void* user) {
  if (!g_state) return;
  std::lock_guard<std::mutex> lk(g_state->mu);
  g_state->execute_cb = cb;
  g_state->execute_user = user;
}

void hvdtpu_set_transport_callback(
    int64_t (*cb)(void*, const uint8_t*, int64_t, int32_t, int32_t,
                  int64_t, uint8_t*, int64_t),
    void* user) {
  if (!g_state) return;
  std::lock_guard<std::mutex> lk(g_state->mu);
  g_state->transport_cb = cb;
  g_state->transport_user = user;
}

// Tuned execution-mode flags of the SINGLE-PROCESS autotuner
// (Response::Flags bits). In MP mode flags ride each planned Response
// (controller.cc CurrentFlags); in SP mode no response crosses a wire,
// so the execute callback reads them here and applies them to the
// executor — without this the tuner could explore hierarchical modes
// whose flag never reached execution.
int32_t hvdtpu_current_flags() {
  if (!g_state) return 0;
  GlobalState& st = *g_state;
  if (!st.param_manager.IsAutoTuning()) return 0;
  int32_t f = 0;
  if (st.param_manager.HierarchicalAllreduce())
    f |= Response::HIERARCHICAL_ALLREDUCE;
  if (st.param_manager.HierarchicalAllgather())
    f |= Response::HIERARCHICAL_ALLGATHER;
  return f;
}

// Flush hint: a submitter about to block on a handle declares the current
// enqueue burst complete — the background cycle drains it NOW (skipping
// the drain debounce and interrupting the pacing sleep) instead of
// waiting for the burst-quiet window. Collapses 1-3 ms of per-step
// control latency in tight synchronous training loops.
void hvdtpu_flush() {
  if (!g_state || !g_state->initialized.load()) return;
  {
    // A waiter with no open scope of its own must not have its hint
    // consumed by a burst scope (see DrainShouldDefer) — mark it
    // foreign so the cycle cuts the scope instead of deferring. Marked
    // regardless of CURRENT depth: a hint landing just before another
    // thread's burst_begin would otherwise be consumed by that scope
    // (the cycle may not run in between). A stale mark with no scope
    // open is cleared by the cycle's no-scope branch. Scope exits set
    // flush_hint directly in hvdtpu_burst_end, never through here, so
    // the per-step exit flush is never mistaken for a foreign waiter.
    std::lock_guard<std::mutex> lk(g_state->burst_owner_mu);
    if (g_state->burst_owners.find(std::this_thread::get_id()) ==
        g_state->burst_owners.end()) {
      g_state->foreign_flush.store(true);
    }
  }
  {
    // Store under cycle_mu: CycleSleep checks the predicate under the
    // same lock, so an unserialized store+notify could land between its
    // check and its block — a lost wakeup that waits out the full cycle.
    std::lock_guard<std::mutex> lk(g_state->cycle_mu);
    g_state->flush_hint.store(true);
  }
  g_state->cycle_cv.notify_all();
}

// Explicit burst scope: between begin and end the cycle will not drain
// the queue (bounded by the max-defer valve), so a multi-tensor
// submission always lands as ONE fusion burst — deterministic group
// composition independent of scheduler timing. end() of the outermost
// scope flushes: the cycle drains immediately.
void hvdtpu_burst_begin() {
  if (!g_state || !g_state->initialized.load()) return;
  {
    std::lock_guard<std::mutex> lk(g_state->burst_owner_mu);
    g_state->burst_owners[std::this_thread::get_id()]++;
  }
  g_state->burst_depth.fetch_add(1);
}

void hvdtpu_burst_end() {
  if (!g_state || !g_state->initialized.load()) return;
  {
    std::lock_guard<std::mutex> lk(g_state->burst_owner_mu);
    auto it = g_state->burst_owners.find(std::this_thread::get_id());
    if (it != g_state->burst_owners.end() && --it->second <= 0) {
      g_state->burst_owners.erase(it);
    }
  }
  if (g_state->burst_depth.fetch_sub(1) <= 1) {
    {
      std::lock_guard<std::mutex> lk(g_state->cycle_mu);  // see hvdtpu_flush
      g_state->flush_hint.store(true);
    }
    g_state->cycle_cv.notify_all();
  }
}

void hvdtpu_set_group_callback(
    void (*cb)(void*, int32_t, const int64_t*, int32_t, int32_t,
               const int64_t*, int32_t, int32_t, const char*),
    void* user) {
  if (!g_state) return;
  std::lock_guard<std::mutex> lk(g_state->mu);
  g_state->group_cb = cb;
  g_state->group_user = user;
}

// Returns handle > 0, or -1 for duplicate name (DUPLICATE_NAME_ERROR,
// operations.cc:270-273), -2 if shut down (SHUT_DOWN_ERROR).
int64_t hvdtpu_enqueue(int32_t op, const char* name, int32_t dtype,
                       const int64_t* shape, int32_t ndims, int32_t root_rank,
                       int32_t device, int64_t nbytes) {
  if (!g_state || !g_state->initialized.load()) return -2;
  GlobalState& st = *g_state;
  if (st.shutdown_requested.load()) return -2;

  PendingEntry pe;
  pe.request.request_rank = st.rank;
  pe.request.request_type = static_cast<Request::Type>(op);
  pe.request.tensor_type = static_cast<DataType>(dtype);
  pe.request.tensor_name = name;
  pe.request.root_rank = root_rank;
  pe.request.device = device;
  std::vector<int64_t> dims(shape, shape + ndims);
  pe.request.tensor_shape = TensorShape(std::move(dims));
  pe.nbytes = nbytes;
  pe.enqueued = Clock::now();

  std::lock_guard<std::mutex> lk(st.mu);
  if (st.tensor_table.count(pe.request.tensor_name)) return -1;
  int64_t h = st.next_handle++;
  pe.handle = h;
  st.handles[h] = HandleState{pe.request.tensor_name, -1, ""};
  st.tensor_table.emplace(pe.request.tensor_name, pe);
  bool was_empty = st.message_queue.empty();
  st.message_queue.push_back(std::move(pe));
  int64_t now = NowNs();
  st.last_enqueue_ns.store(now);
  if (was_empty) st.oldest_enqueue_ns.store(now);
  if (st.timeline.Initialized()) {
    st.timeline.NegotiateStart(name, op);
    st.timeline.NegotiateRankReady(name, st.rank);
  }
  return h;
}

// Python reports group completion. status_type: StatusType values; reason
// used when != OK.
void hvdtpu_complete(const int64_t* handles, int32_t count,
                     int32_t status_type, const char* reason) {
  if (!g_state) return;
  GlobalState& st = *g_state;
  std::vector<std::string> names;
  {
    std::lock_guard<std::mutex> lk(st.mu);
    for (int i = 0; i < count; ++i) {
      auto it = st.handles.find(handles[i]);
      if (it == st.handles.end()) continue;
      it->second.status = status_type;
      it->second.reason = reason ? reason : "";
      names.push_back(it->second.name);
      st.tensor_table.erase(it->second.name);
    }
  }
  if (st.timeline.Initialized()) {
    for (const auto& n : names) {
      st.timeline.ActivityEnd(n);   // close QUEUE/XLA activity
      st.timeline.End(n, "");
    }
  }
}

// Poll handle: -1 in flight, else StatusType value (PollHandle,
// torch/handle_manager.cc:21-50).
int32_t hvdtpu_poll(int64_t handle) {
  if (!g_state) return static_cast<int32_t>(StatusType::ABORTED);
  std::lock_guard<std::mutex> lk(g_state->mu);
  auto it = g_state->handles.find(handle);
  if (it == g_state->handles.end())
    return static_cast<int32_t>(StatusType::INVALID_ARGUMENT);
  return it->second.status;
}

void hvdtpu_release_handle(int64_t handle) {
  if (!g_state) return;
  std::lock_guard<std::mutex> lk(g_state->mu);
  g_state->handles.erase(handle);
}

int hvdtpu_rank() { return g_state ? g_state->rank : -1; }
int hvdtpu_size() { return g_state ? g_state->size : -1; }
int hvdtpu_local_size() { return g_state ? g_state->local_size : -1; }

void hvdtpu_set_fusion_threshold(int64_t bytes) {
  if (g_state) g_state->fusion_threshold.store(bytes);
}
int64_t hvdtpu_get_fusion_threshold() {
  return g_state ? g_state->fusion_threshold.load() : -1;
}
void hvdtpu_set_cycle_time_ms(double ms) {
  if (g_state)
    g_state->cycle_time_us.store(static_cast<int64_t>(ms * 1000));
}
double hvdtpu_get_cycle_time_ms() {
  return g_state ? g_state->cycle_time_us.load() / 1000.0 : -1;
}

// Timeline bridge for Python-side activities (XLA launch/wait phases).
void hvdtpu_timeline_activity_start(const char* tensor,
                                    const char* activity) {
  if (g_state) g_state->timeline.ActivityStart(tensor, activity);
}
void hvdtpu_timeline_activity_end(const char* tensor) {
  if (g_state) g_state->timeline.ActivityEnd(tensor);
}
int hvdtpu_timeline_enabled() {
  return g_state && g_state->timeline.Initialized() ? 1 : 0;
}

// Autotune inspection (test / observability surface).
int hvdtpu_autotune_active() {
  return g_state && g_state->param_manager.IsAutoTuning() &&
                 !g_state->param_manager.IsDone()
             ? 1 : 0;
}
int hvdtpu_autotune_done() {
  return g_state && g_state->param_manager.IsDone() ? 1 : 0;
}

// Host staging arena (FusionBufferManager bridge).
uint8_t* hvdtpu_fusion_buffer(int device, int64_t threshold) {
  return g_state ? g_state->fusion_buffers.GetBuffer(device, threshold)
                 : nullptr;
}

// ---- wire protocol + negotiation test surface (used by pytest via ctypes
// and by the multi-host controller) ----------------------------------------

int64_t hvdtpu_wire_roundtrip_request_list(const uint8_t* in, int64_t in_len,
                                           uint8_t* out, int64_t out_cap) {
  RequestList rl;
  if (!RequestList::ParseFrom(in, static_cast<size_t>(in_len), &rl)) return -1;
  std::vector<uint8_t> buf;
  rl.SerializeTo(&buf);
  if (static_cast<int64_t>(buf.size()) > out_cap) return -1;
  std::memcpy(out, buf.data(), buf.size());
  return static_cast<int64_t>(buf.size());
}

// Build a serialized Request for tests / the controller client.
int64_t hvdtpu_wire_make_request(int32_t rank, int32_t op, int32_t dtype,
                                 const char* name, int32_t root_rank,
                                 int32_t device, const int64_t* shape,
                                 int32_t ndims, uint8_t* out,
                                 int64_t out_cap) {
  Request r;
  r.request_rank = rank;
  r.request_type = static_cast<Request::Type>(op);
  r.tensor_type = static_cast<DataType>(dtype);
  r.tensor_name = name;
  r.root_rank = root_rank;
  r.device = device;
  r.tensor_shape = TensorShape(std::vector<int64_t>(shape, shape + ndims));
  std::vector<uint8_t> buf;
  r.SerializeTo(&buf);
  if (static_cast<int64_t>(buf.size()) > out_cap) return -1;
  std::memcpy(out, buf.data(), buf.size());
  return static_cast<int64_t>(buf.size());
}

// Run coordinator validation over a batch of serialized Requests (size =
// world size). Writes the Response error message (or "") to err; returns
// the Response type.
int32_t hvdtpu_negotiate(const uint8_t* data, int64_t len, int32_t nreq,
                         int32_t world_size, char* err, int64_t err_cap,
                         int64_t* tensor_sizes_out, int32_t sizes_cap) {
  std::vector<Request> reqs;
  size_t off = 0;
  for (int i = 0; i < nreq; ++i) {
    Request r;
    size_t consumed;
    if (!Request::ParseFrom(data + off, static_cast<size_t>(len) - off,
                            &consumed, &r)) {
      std::snprintf(err, err_cap, "parse error at request %d", i);
      return static_cast<int32_t>(Response::ERROR);
    }
    off += consumed;
    reqs.push_back(std::move(r));
  }
  Response resp = ConstructResponse(reqs, world_size);
  std::snprintf(err, err_cap, "%s", resp.error_message.c_str());
  int32_t n = std::min<int32_t>(sizes_cap,
                                static_cast<int32_t>(resp.tensor_sizes.size()));
  for (int32_t i = 0; i < n; ++i) tensor_sizes_out[i] = resp.tensor_sizes[i];
  return static_cast<int32_t>(resp.response_type);
}

// half/bf16 conversion surface (N8 parity; exercised by tests).
void hvdtpu_half_to_float(const uint16_t* in, float* out, int64_t n) {
  for (int64_t i = 0; i < n; ++i) out[i] = HalfBits2Float(in[i]);
}
void hvdtpu_float_to_half(const float* in, uint16_t* out, int64_t n) {
  for (int64_t i = 0; i < n; ++i) out[i] = Float2HalfBits(in[i]);
}
void hvdtpu_halfsum(const uint16_t* src, uint16_t* dst, int64_t n) {
  HalfSum(src, dst, static_cast<size_t>(n));
}
void hvdtpu_bf16sum(const uint16_t* src, uint16_t* dst, int64_t n) {
  BF16Sum(src, dst, static_cast<size_t>(n));
}

}  // extern "C"
