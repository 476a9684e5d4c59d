// dt_core — native host core for diamond_types_tpu.
//
// Implements the merge-critical host path in C++ (the reference implements
// this tier in Rust; see SURVEY.md §2 native-component note):
//   * columnar causal graph + DAG queries (diff / find_conflicting)
//     (reference: src/causalgraph/graph/tools.rs)
//   * frontier movement (reference: src/frontier.rs)
//   * spanning-tree conflict walker (reference: src/listmerge/txn_trace.rs)
//   * treap-based merge tracker with dual current/upstream aggregates and
//     YjsMod integrate (reference: src/listmerge/merge.rs, yjsspan.rs,
//     advance_retreat.rs — same design as the Python tracker in
//     diamond_types_tpu/listmerge/tracker.py)
//   * the transformed-op pipeline incl. fast-forward mode
//     (reference: src/listmerge/merge.rs:585-941)
//
// Exposed as a C ABI consumed via ctypes (no pybind11 in this image).
// Content (text) stays on the Python side; this library deals purely in
// LV spans and positions.

#include <algorithm>
#include <array>
#include <cassert>
#include <cstdint>
#include <cstring>
#include <deque>
#include <memory>
#include <queue>
#include <string>
#include <vector>

typedef int64_t i64;
typedef uint8_t u8;

static const i64 ROOT = -1;
static const i64 UNDERWATER = 1ll << 62;

// ---------------------------------------------------------------- utilities

#ifdef DT_PROF
static long g_diff_calls = 0, g_diff_iters = 0;
long g_walk_steps = 0, g_walk_zero = 0, g_diff_iters2 = 0;
long g_orr_iters = 0;
#endif

// Always-on structured event counters around the merge kernel (SURVEY §5:
// the reference sketches these in its hot loops, merge.rs:311-314 /
// advance_retreat.rs:73-76; here they ship enabled — plain increments cost
// nothing next to the work they count). Exported via dt_get_counters; the
// name order is mirrored by native/core.py EVENT_COUNTER_NAMES.
struct EventCounters {
  unsigned long long integrate_calls = 0, integrate_scan_iters = 0,
      apply_ins_runs = 0, apply_del_runs = 0, advance_calls = 0,
      retreat_calls = 0, walk_steps = 0, diff_calls = 0;
};
static EventCounters g_events;

struct Span { i64 start, end; };

// A dense LV -> entry map brought up to date after its RLE column was cut
// back to `from` entries and grown again to `n`. LVs below `keep` map as
// before: those of the entries that stayed and, where only the last entry
// was sent again (the same or longer), that entry's old ones.
template <class SpanOf>
static void refill_idx(std::vector<int32_t>& idx_of, size_t from, size_t n,
                       i64 keep, SpanOf span_of) {
  idx_of.resize(n ? (size_t)span_of(n - 1).end : 0);
  for (size_t i = from; i < n; i++) {
    Span s = span_of(i);
    for (i64 v = std::max(s.start, keep); v < s.end; v++)
      idx_of[v] = (int32_t)i;
  }
}

// What `refill_idx` may keep when a column of `n_old` entries over LVs
// [.., top) is cut back to `from` (its entry `from` starting at
// `cut_start`) and `n_new` entries follow, or -1 where they do not
// continue it. The log only appends, so the first entry cut comes back:
// what follows starts where it started or, with nothing cut, at `top`;
// and where only the last entry was cut, it is as long as it was or longer.
static i64 keep_below(size_t from, size_t n_old, i64 cut_start, i64 top,
                      i64 n_new, i64 new_start, i64 new_first_end) {
  if (from > n_old) return -1;
  if (n_new == 0) return from == n_old ? top : -1;
  i64 expect = from < n_old ? cut_start : top;
  if (new_start != expect) return -1;
  if (from + 1 == n_old) return new_first_end >= top ? top : -1;
  return expect;
}

static inline bool span_empty(const Span& s) { return s.end <= s.start; }

static void push_reversed_rle(std::vector<Span>& out, Span s) {
  if (!out.empty() && s.end == out.back().start) out.back().start = s.start;
  else out.push_back(s);
}

// ---------------------------------------------------------------- graph

struct Graph {
  std::vector<i64> starts, ends, shadows;
  // parents in CSR layout (flat + indptr) for cache-friendly iteration
  std::vector<i64> pindptr, pflat;
  // dense LV -> entry index (LVs are 0..ends.back())
  std::vector<int32_t> idx_of;
  // diff-hot per-entry data packed in one line: start + inline parents
  struct DiffEnt { i64 start; int32_t np; i64 p[2]; };
  std::vector<DiffEnt> dent;

  inline size_t pn(size_t i) const { return pindptr[i + 1] - pindptr[i]; }
  inline const i64* pb(size_t i) const { return pflat.data() + pindptr[i]; }

  // The graph's version frontier (ascending): every entry-final LV that
  // no other entry references as a parent. Used by transform's trivial
  // checkout fast path (from=[] merging the full graph).
  std::vector<i64> heads;

  // idx_of, dent and heads after the columns were cut back to `from`
  // entries and grown again (see refill_idx for `keep`). The entries that
  // came back name at least the parents the cut ones named, so a head
  // they had struck stays struck.
  void reindex(size_t from, i64 keep) {
    size_t n = starts.size();
    refill_idx(idx_of, from, n, keep,
               [&](size_t i) { return Span{starts[i], ends[i]}; });
    dent.resize(n);
    if (from < n)
      heads.erase(std::lower_bound(heads.begin(), heads.end(), starts[from]),
                  heads.end());
    for (size_t i = from; i < n; i++) {
      dent[i].start = starts[i];
      size_t np = pn(i);
      dent[i].np = (int32_t)np;
      for (size_t k = 0; k < np && k < 2; k++) dent[i].p[k] = pb(i)[k];
      // an entry's last LV is a head until a later entry names it
      for (size_t k = 0; k < np; k++) {
        auto it = std::lower_bound(heads.begin(), heads.end(), pb(i)[k]);
        if (it != heads.end() && *it == pb(i)[k]) heads.erase(it);
      }
      heads.push_back(ends[i] - 1);
    }
  }

  inline size_t find_idx(i64 v) const { return idx_of[v]; }

  void parents_at(i64 v, std::vector<i64>& out) const {
    size_t i = find_idx(v);
    out.clear();
    if (v > starts[i]) out.push_back(v - 1);
    else out.assign(pb(i), pb(i) + pn(i));
  }

  bool entry_contains(size_t idx, i64 v) const {
    return starts[idx] <= v && v < ends[idx];
  }

  bool is_direct_descendant_coarse(i64 a, i64 b) const {
    if (a == b || b == ROOT) return true;
    return a > b && entry_contains(find_idx(a), b);
  }

  mutable std::vector<i64> fcv_heap;

  bool frontier_contains_version(const std::vector<i64>& f, i64 target) const {
    if (target == ROOT) return true;
    for (i64 o : f) if (o == target) return true;
    if (f.empty()) return false;
    for (i64 o : f) if (o > target && shadows[find_idx(o)] <= target) return true;
    std::vector<i64>& q = fcv_heap;
    q.clear();
    for (i64 o : f) if (o > target) q.push_back(o);
    std::make_heap(q.begin(), q.end());
    while (!q.empty()) {
      i64 order = q.front();
      std::pop_heap(q.begin(), q.end()); q.pop_back();
      size_t i = find_idx(order);
      if (shadows[i] <= target) return true;
      i64 start = starts[i];
      while (!q.empty() && q.front() >= start) {
        std::pop_heap(q.begin(), q.end()); q.pop_back();
      }
      for (size_t k = 0; k < pn(i); k++) {
        i64 p = pb(i)[k];
        if (p == target) return true;
        if (p > target) {
          q.push_back(p); std::push_heap(q.begin(), q.end());
        }
      }
    }
    return false;
  }

  // diff: returns (only_a, only_b) in DESCENDING order.
  enum Flag : u8 { OnlyA = 0, OnlyB = 1, Shared = 2 };

  void diff_rev(const std::vector<i64>& a, const std::vector<i64>& b,
                std::vector<Span>& only_a, std::vector<Span>& only_b) const {
    only_a.clear(); only_b.clear();
    if (a == b) return;
    if (a.size() == 1 && b.size() == 1) {
      if (is_direct_descendant_coarse(a[0], b[0])) {
        if (a[0] != b[0]) only_a.push_back({b[0] + 1, a[0] + 1});
        return;
      }
      if (is_direct_descendant_coarse(b[0], a[0])) {
        only_b.push_back({a[0] + 1, b[0] + 1});
        return;
      }
    }
    diff_slow(a, b, only_a, only_b);
  }

  mutable std::vector<std::pair<i64, u8>> diff_heap;

  void diff_slow(const std::vector<i64>& a, const std::vector<i64>& b,
                 std::vector<Span>& only_a, std::vector<Span>& only_b) const {
    // max-heap of (lv, flag)
    std::vector<std::pair<i64, u8>>& q = diff_heap;
    g_events.diff_calls++;
#ifdef DT_PROF
    g_diff_calls++;
#endif
    q.clear();
    for (i64 v : a) q.push_back({v, OnlyA});
    for (i64 v : b) q.push_back({v, OnlyB});
    std::make_heap(q.begin(), q.end());
    long num_shared = 0;

    auto mark = [&](i64 lo, i64 hi, u8 flag) {
      if (flag == Shared) return;
      push_reversed_rle(flag == OnlyA ? only_a : only_b, {lo, hi + 1});
    };
    auto pop = [&]() { std::pop_heap(q.begin(), q.end()); q.pop_back(); };
    auto push = [&](i64 v, u8 f) {
      q.push_back({v, f}); std::push_heap(q.begin(), q.end());
    };

    while (!q.empty()) {
#ifdef DT_PROF
      g_diff_iters++;
#endif
      auto [ord, flag] = q.front(); pop();
      if (flag == Shared) num_shared--;
      while (!q.empty() && q.front().first == ord) {
        u8 pf = q.front().second; pop();
        if (pf != flag) flag = Shared;
        if (pf == Shared) num_shared--;
      }
      size_t i = find_idx(ord);
      const DiffEnt& de = dent[i];
      i64 start = de.start;
      while (!q.empty() && q.front().first >= start) {
        i64 peek_ord = q.front().first; u8 pf = q.front().second;
        if (pf != flag) {
          mark(peek_ord + 1, ord, flag);
          ord = peek_ord;
          flag = Shared;
        }
        if (pf == Shared) num_shared--;
        pop();
      }
      mark(start, ord, flag);
      const i64* pp = de.np <= 2 ? de.p : pb(i);
      for (int32_t k = 0; k < de.np; k++) {
        push(pp[k], flag);
        if (flag == Shared) num_shared++;
      }
      if ((long)q.size() == num_shared) break;
    }
  }

  // find_conflicting; visits spans (descending); returns common ancestor.
  template <class V>
  std::vector<i64> find_conflicting(const std::vector<i64>& a,
                                    const std::vector<i64>& b, V visit) const {
    if (a == b) return a;
    if (a.size() == 1 && b.size() == 1) {
      if (is_direct_descendant_coarse(a[0], b[0])) {
        if (a[0] != b[0]) visit(Span{b[0] + 1, a[0] + 1}, (u8)OnlyA);
        return b[0] == ROOT ? std::vector<i64>{} : std::vector<i64>{b[0]};
      }
      if (is_direct_descendant_coarse(b[0], a[0])) {
        visit(Span{a[0] + 1, b[0] + 1}, (u8)OnlyB);
        return a[0] == ROOT ? std::vector<i64>{} : std::vector<i64>{a[0]};
      }
    }
    return find_conflicting_slow(a, b, visit);
  }

  struct TimePoint {
    i64 last;
    std::vector<i64> merged;  // sorted, excludes last
    bool operator==(const TimePoint& o) const {
      return last == o.last && merged == o.merged;
    }
    // max-heap: highest last first; among equal, FEWER merged first.
    bool operator<(const TimePoint& o) const {
      if (last != o.last) return last < o.last;
      if (merged.size() != o.merged.size()) return merged.size() > o.merged.size();
      return merged < o.merged;
    }
  };

  template <class V>
  std::vector<i64> find_conflicting_slow(const std::vector<i64>& a,
                                         const std::vector<i64>& b,
                                         V visit) const {
    auto tp = [](const std::vector<i64>& f) {
      TimePoint t;
      if (f.empty()) { t.last = ROOT; return t; }
      t.last = f.back();
      t.merged.assign(f.begin(), f.end() - 1);
      return t;
    };
    auto tpp = [this](size_t i) {
      TimePoint t;
      size_t n = pn(i);
      if (n == 0) { t.last = ROOT; return t; }
      t.last = pb(i)[n - 1];
      t.merged.assign(pb(i), pb(i) + n - 1);
      return t;
    };
    std::priority_queue<std::pair<TimePoint, u8>> q;
    q.push({tp(a), OnlyA});
    q.push({tp(b), OnlyB});

    while (true) {
      auto [time, flag] = q.top(); q.pop();
      i64 t = time.last;
      if (t == ROOT) return {};
      while (!q.empty() && q.top().first == time) {
        if (q.top().second != flag) flag = Shared;
        q.pop();
      }
      if (q.empty()) {
        std::vector<i64> fr = time.merged;
        fr.push_back(t);
        return fr;
      }
      for (i64 t2 : time.merged) q.push({TimePoint{t2, {}}, flag});
      size_t i = find_idx(t);
      Span rng{starts[i], t + 1};
      while (true) {
        if (!q.empty()) {
          const TimePoint& peek = q.top().first;
          if (peek.last != ROOT && peek.last >= starts[i]) {
            auto [time2, next_flag] = q.top(); q.pop();
            if (time2.last + 1 < rng.end) {
              i64 offset = time2.last + 1 - starts[i];
              Span rem{starts[i] + offset, rng.end};
              rng = {starts[i], starts[i] + offset};
              visit(rem, flag);
            }
            for (i64 t2 : time2.merged) q.push({TimePoint{t2, {}}, next_flag});
            if (next_flag != flag) flag = Shared;
          } else {
            visit(rng, flag);
            q.push({tpp(i), flag});
            break;
          }
        } else {
          return {rng.end - 1};
        }
      }
    }
  }

  // frontier ops (reference: src/frontier.rs)
  void advance_known_run(std::vector<i64>& f, const std::vector<i64>& ps,
                         Span span) const {
    i64 last = span.end - 1;
    if (ps.size() == 1 && f.size() == 1 && ps[0] == f[0]) { f[0] = last; return; }
    if (f == ps) { f.assign(1, last); return; }
    std::vector<i64> out;
    for (i64 o : f)
      if (std::find(ps.begin(), ps.end(), o) == ps.end()) out.push_back(o);
    out.insert(std::upper_bound(out.begin(), out.end(), last), last);
    f = out;
  }

  // parents scratch for advance/retreat: one malloc per merge instead of
  // one per call (transform advances the frontier once per walk step).
  // Contexts are driven single-threaded (the Python side serializes per
  // oplog), so a mutable scratch on a const method is safe here.
  mutable std::vector<i64> ps_scratch;

  void advance(std::vector<i64>& f, Span rng) const {
    i64 start = rng.start;
    size_t i = find_idx(start);
    std::vector<i64>& ps = ps_scratch;
    while (true) {
      i64 e_end = std::min(ends[i], rng.end);
      parents_at(start, ps);
      advance_known_run(f, ps, {start, e_end});
      if (e_end >= rng.end) break;
      start = e_end;
      i++;
    }
  }

  void retreat(std::vector<i64>& f, Span rng) const {
    if (span_empty(rng)) return;
    i64 start = rng.start, end = rng.end;
    size_t i = find_idx(end - 1);
    std::vector<i64>& ps = ps_scratch;
    while (true) {
      i64 last_order = end - 1;
      i64 t_start = starts[i];
      if (f.size() == 1) {
        if (start > t_start) { f[0] = start - 1; break; }
        f.assign(pb(i), pb(i) + pn(i));
      } else {
        f.erase(std::remove(f.begin(), f.end(), last_order), f.end());
        parents_at(std::max(start, t_start), ps);
        for (i64 p : ps) {
          if (!frontier_contains_version(f, p))
            f.insert(std::upper_bound(f.begin(), f.end(), p), p);
        }
      }
      if (start >= t_start) break;
      end = t_start;
      i--;
    }
  }
};

// ---------------------------------------------------------------- agents

struct AgentRun { i64 seq_start, seq_end, lv_start; };

struct Agents {
  std::vector<std::string> names;
  std::vector<std::vector<AgentRun>> client_runs;
  // global: (lv_start, lv_end, agent, seq_start), lv-sorted
  struct GRun { i64 lv0, lv1; i64 agent, seq0; };
  std::vector<GRun> global_runs;

  std::vector<int32_t> idx_of;  // dense LV -> global run index

  inline const GRun& find_global(i64 lv) const {
    if (lv < (i64)idx_of.size()) return global_runs[idx_of[lv]];
    size_t lo = 0, hi = global_runs.size();
    while (lo < hi) { size_t mid = (lo + hi) / 2;
      if (global_runs[mid].lv0 <= lv) lo = mid + 1; else hi = mid; }
    return global_runs[lo - 1];
  }

  void local_to_agent(i64 lv, i64& agent, i64& seq) const {
    const GRun& g = find_global(lv);
    agent = g.agent;
    seq = g.seq0 + (lv - g.lv0);
  }

  i64 span_len(i64 lv, i64 max_len) const {
    const GRun& g = find_global(lv);
    return std::min(g.lv1 - lv, max_len);
  }
};

// ---------------------------------------------------------------- op store

struct OpRun { i64 lv; u8 kind; u8 fwd; i64 start, end; i64 cp; };
static const u8 INS = 0, DEL = 1;

struct Ops {
  std::vector<OpRun> runs;
  std::vector<int32_t> idx_of;  // dense LV -> run index

  inline size_t find_idx(i64 lv) const {
    if (lv < (i64)idx_of.size()) return idx_of[lv];
    size_t lo = 0, hi = runs.size();
    while (lo < hi) { size_t mid = (lo + hi) / 2;
      if (runs[mid].lv <= lv) lo = mid + 1; else hi = mid; }
    return lo - 1;
  }

  // sub-run covering item offsets [o0, o1) of run r
  static OpRun slice(const OpRun& r, i64 o0, i64 o1) {
    i64 n = r.end - r.start;
    if (o0 == 0 && o1 == n) return r;
    OpRun out = r;
    out.lv = r.lv + o0;
    if (r.cp >= 0) out.cp = r.cp + o0;
    i64 s, e;
    if (r.kind == INS) {
      s = r.start + o0; e = s + (o1 - o0);
    } else if (r.fwd) {
      s = r.start; e = s + (o1 - o0);
    } else {
      s = r.end - o1; e = r.end - o0;
    }
    out.start = s; out.end = e;
    return out;
  }
};

// ---------------------------------------------------------------- tracker
//
// Fat-leaf order-statistic B-tree of YjsSpan runs, the same design as the
// reference's content-tree (crates/content-tree/src/lib.rs:64, node sizes
// :33-41) with the dual current/upstream metric (src/listmerge/metrics.rs:
// 18-66). The LV -> leaf "space index" (reference: src/listmerge/markers.rs
// MarkerEntry / InsPtr) is a B+ tree of RLE runs keyed by LV, updated by a
// notify hook when entries move between leaves.

struct BLeaf;

// One YjsSpan run (reference: src/listmerge/yjsspan.rs:25-45).
struct BEntry {
  i64 ids;        // id (LV) of first item
  i64 len;
  i64 ol, orr;    // origin left / right
  int32_t state;  // 0 NIY, 1 inserted, >=2 deleted (state-1) times
  bool ever;
  inline i64 ide() const { return ids + len; }
  inline i64 cur() const { return state == 1 ? len : 0; }
  inline i64 up() const { return ever ? 0 : len; }
  inline i64 origin_left_at(i64 off) const {
    return off == 0 ? ol : ids + off - 1;
  }
};

static const int LEAF_CAP = 32;   // entries per leaf (16 was best for the
// FF-era workload; the round-5 zone-everything merge pushes whole
// histories through the tracker and re-measured best at 32 — nn -17%)
static const int NODE_CAP = 16;   // children per internal node

struct BNode;

struct BLeaf {
  int n = 0;
  BNode* parent = nullptr;
  int pslot = 0;
  BLeaf *next = nullptr, *prev = nullptr;
  BEntry e[LEAF_CAP];
};

struct BNode {
  int n = 0;
  bool leaf_children = true;
  BNode* parent = nullptr;
  int pslot = 0;
  void* ch[NODE_CAP];
  i64 raw[NODE_CAP], cur[NODE_CAP], up[NODE_CAP];
};

// ---- LV -> BLeaf* index: B+ tree of RLE runs keyed by LV ----

struct IRun { i64 key, len; BLeaf* ptr; };
static const int IL_CAP = 32;
static const int IN_CAP = 16;

struct INodeI;
struct ILeaf {
  int n = 0;
  INodeI* parent = nullptr;
  int pslot = 0;
  ILeaf *next = nullptr, *prev = nullptr;
  IRun r[IL_CAP];
};
struct INodeI {
  int n = 0;
  bool leaf_children = true;
  INodeI* parent = nullptr;
  int pslot = 0;
  i64 k0[IN_CAP];
  void* ch[IN_CAP];
};

struct SpaceIndex {
  std::deque<ILeaf> leaf_pool;
  std::deque<INodeI> node_pool;
  INodeI* root;

  SpaceIndex() {
    leaf_pool.emplace_back();
    node_pool.emplace_back();
    root = &node_pool.back();
    root->leaf_children = true;
    root->n = 1;
    root->k0[0] = INT64_MIN;
    root->ch[0] = &leaf_pool.back();
    leaf_pool.back().parent = root;
  }

  ILeaf* descend(i64 key) const {
    INodeI* nd = root;
    while (true) {
      int i = nd->n - 1;
      while (i > 0 && nd->k0[i] > key) i--;
      if (nd->leaf_children) {
        ILeaf* lf = (ILeaf*)nd->ch[i];
        // separators can be stale-low; the containing run may live in an
        // earlier leaf (see set_range erase semantics).
        while (lf->prev && (lf->n == 0 || key < lf->r[0].key)) lf = lf->prev;
        return lf;
      }
      nd = (INodeI*)nd->ch[i];
    }
  }

  BLeaf* query(i64 key) const {
    ILeaf* lf = descend(key);
    int lo = 0, hi = lf->n;
    while (lo < hi) { int mid = (lo + hi) / 2;
      if (lf->r[mid].key <= key) lo = mid + 1; else hi = mid; }
    assert(lo > 0 && key < lf->r[lo - 1].key + lf->r[lo - 1].len);
    return lf->r[lo - 1].ptr;
  }

  void split_inode(INodeI* nd) {
    while (nd->n == IN_CAP) {
      node_pool.emplace_back();
      INodeI* rn = &node_pool.back();
      int half = IN_CAP / 2;
      rn->leaf_children = nd->leaf_children;
      rn->n = IN_CAP - half;
      for (int i = 0; i < rn->n; i++) {
        rn->k0[i] = nd->k0[half + i];
        rn->ch[i] = nd->ch[half + i];
        if (rn->leaf_children) {
          ((ILeaf*)rn->ch[i])->parent = rn; ((ILeaf*)rn->ch[i])->pslot = i;
        } else {
          ((INodeI*)rn->ch[i])->parent = rn; ((INodeI*)rn->ch[i])->pslot = i;
        }
      }
      nd->n = half;
      INodeI* par = nd->parent;
      if (!par) {
        node_pool.emplace_back();
        INodeI* nr = &node_pool.back();
        nr->leaf_children = false;
        nr->n = 2;
        nr->k0[0] = nd->k0[0]; nr->ch[0] = nd;
        nr->k0[1] = rn->k0[0]; nr->ch[1] = rn;
        nd->parent = nr; nd->pslot = 0;
        rn->parent = nr; rn->pslot = 1;
        root = nr;
        return;
      }
      int at = nd->pslot + 1;
      for (int i = par->n; i > at; i--) {
        par->k0[i] = par->k0[i - 1]; par->ch[i] = par->ch[i - 1];
        if (par->leaf_children) ((ILeaf*)par->ch[i])->pslot = i;
        else ((INodeI*)par->ch[i])->pslot = i;
      }
      par->k0[at] = rn->k0[0];
      par->ch[at] = rn;
      rn->parent = par; rn->pslot = at;
      par->n++;
      nd = par;
    }
  }

  // Insert run at position `at` in leaf lf (splitting the leaf if full).
  void insert_run(ILeaf* lf, int at, IRun run) {
    if (lf->n == IL_CAP) {
      leaf_pool.emplace_back();
      ILeaf* rn = &leaf_pool.back();
      int half = IL_CAP / 2;
      rn->n = IL_CAP - half;
      std::memcpy(rn->r, lf->r + half, rn->n * sizeof(IRun));
      lf->n = half;
      rn->next = lf->next; if (rn->next) rn->next->prev = rn;
      rn->prev = lf; lf->next = rn;
      INodeI* par = lf->parent;
      if (par->n == IN_CAP) { split_inode(par); par = lf->parent; }
      int slot = lf->pslot + 1;
      for (int i = par->n; i > slot; i--) {
        par->k0[i] = par->k0[i - 1]; par->ch[i] = par->ch[i - 1];
        ((ILeaf*)par->ch[i])->pslot = i;
      }
      par->k0[slot] = rn->r[0].key;
      par->ch[slot] = rn;
      rn->parent = par; rn->pslot = slot;
      par->n++;
      if (at > half) { at -= half; lf = rn; }
    }
    for (int i = lf->n; i > at; i--) lf->r[i] = lf->r[i - 1];
    lf->r[at] = run;
    lf->n++;
  }

  // Location-returning insert (position of the inserted run).
  std::pair<ILeaf*, int> insert_run_ret(ILeaf* lf, int at, IRun run) {
    if (lf->n == IL_CAP) {
      // same split as insert_run, but track where `at` lands
      insert_run(lf, at, run);
      // find it again (rare path): run.key uniquely identifies it
      ILeaf* l2 = lf;
      while (l2) {
        for (int i = 0; i < l2->n; i++)
          if (l2->r[i].key == run.key) return {l2, i};
        l2 = l2->next;
      }
      assert(false);
      return {lf, at};
    }
    for (int i = lf->n; i > at; i--) lf->r[i] = lf->r[i - 1];
    lf->r[at] = run;
    lf->n++;
    return {lf, at};
  }

  // Remove all coverage of [key, end). Returns the location where a run
  // starting at `key` should be inserted to keep global key order.
  std::pair<ILeaf*, int> erase_range(i64 key, i64 end) {
    ILeaf* lf = descend(key);
    int lo = 0, hi = lf->n;
    while (lo < hi) { int mid = (lo + hi) / 2;
      if (lf->r[mid].key <= key) lo = mid + 1; else hi = mid; }
    int at = lo;  // first run with r.key > key
    if (at > 0) {
      IRun& pv = lf->r[at - 1];
      i64 pend = pv.key + pv.len;
      if (pend > key) {  // pv overlaps [key, ..)
        if (pv.key == key) {
          if (pend > end) {
            pv.key = end; pv.len = pend - end;
            return {lf, at - 1};
          }
          for (int i = at - 1; i < lf->n - 1; i++) lf->r[i] = lf->r[i + 1];
          lf->n--; at--;
        } else {
          pv.len = key - pv.key;
          if (pend > end) {
            // hole carved in the middle of pv: keep the tail
            return insert_run_ret(lf, at, IRun{end, pend - end, pv.ptr});
          }
        }
      }
    }
    // remove following runs fully covered; trim a partial overlap
    while (true) {
      if (at == lf->n) {
        ILeaf* nx = lf->next;
        if (!nx) return {lf, at};
        if (nx->n == 0) { lf = nx; at = 0; continue; }
        if (nx->r[0].key >= end) return {lf, at};
        lf = nx; at = 0;
        continue;
      }
      IRun& r = lf->r[at];
      if (r.key >= end) return {lf, at};
      i64 rend = r.key + r.len;
      if (rend <= end) {
        for (int i = at; i < lf->n - 1; i++) lf->r[i] = lf->r[i + 1];
        lf->n--;
      } else {
        r.len = rend - end;
        r.key = end;
        return {lf, at};
      }
    }
  }

  // Overwrite [key, key+len) to map to ptr.
  void set_range(i64 key, i64 len, BLeaf* ptr) {
    i64 end = key + len;
    auto [lf, at] = erase_range(key, end);
    // merge with left neighbor
    IRun* pv = nullptr;
    ILeaf* plf = nullptr;
    if (at > 0) { pv = &lf->r[at - 1]; plf = lf; }
    else if (lf->prev && lf->prev->n) {
      plf = lf->prev; pv = &plf->r[plf->n - 1];
    }
    if (pv && pv->key + pv->len == key && pv->ptr == ptr) {
      pv->len += len;
      // absorb right neighbor too if now contiguous
      if (at < lf->n && lf->r[at].key == end && lf->r[at].ptr == ptr &&
          plf == lf) {
        pv->len += lf->r[at].len;
        for (int i = at; i < lf->n - 1; i++) lf->r[i] = lf->r[i + 1];
        lf->n--;
      }
      return;
    }
    // merge with right neighbor
    if (at < lf->n && lf->r[at].key == end && lf->r[at].ptr == ptr) {
      lf->r[at].key = key; lf->r[at].len += len;
      return;
    }
    insert_run(lf, at, IRun{key, len, ptr});
  }
};

struct Cursor { BLeaf* leaf; int idx; i64 off; };  // leaf==nullptr => doc end

struct DelRow { i64 lv0, lv1, t0, t1; bool fwd; };

struct Tracker {
  std::deque<BLeaf> leaf_pool;
  std::deque<BNode> node_pool;
  BNode* root;
  BLeaf* first_leaf;
  // LV -> containing tree leaf, split by range: op LVs are dense in
  // [0, ops_top) -> O(1) table; underwater placeholder ids (>= 1<<62,
  // origin-right sentinels and pre-existing text hit by concurrent
  // deletes) -> small RLE B+ tree. Together they replace the reference's
  // marker tree InsPtr half (src/listmerge/markers.rs).
  std::vector<BLeaf*> leaf_of;
  SpaceIndex uw_index;
  // delete targets: op LVs are dense, so an O(1) run table replaces the
  // reference's marker-tree DelTarget entries (src/listmerge/markers.rs)
  std::vector<DelRow> del_list;
  std::vector<int32_t> del_run_of;  // op lv -> del_list index, -1 = none
  // Genuinely colliding concurrent inserts seen by integrate (reference:
  // merge_conflict_checks, listmerge/mod.rs:50-51 — counted whenever the
  // scan meets another item that is not simply our origin-right).
  i64 collisions = 0;

  // Forward-delete continuation memo: a long delete run is applied in
  // entry-bounded chunks with an unchanged current position (the text
  // shifts left under it, Ops::slice keeps .start fixed for fwd deletes).
  // After a partial chunk we stash the rolled-forward cursor + upstream
  // prefix so the continuation call skips the root descent. Invalidated by
  // any other tree mutation (inserts, toggles, reverse deletes).
  i64 del_cont_pos = -1;
  i64 del_cont_up = 0;
  Cursor del_cont_cursor{nullptr, 0, 0};

  // Dense tables cover only [base, ops_top) — the conflict zone's LV
  // range — so per-merge cost scales with the zone, not the full history.
  i64 base;

  explicit Tracker(i64 zone_base, i64 ops_top) : base(zone_base) {
    del_run_of.assign((size_t)(ops_top - base), -1);
    leaf_of.assign((size_t)(ops_top - base), nullptr);
    leaf_pool.emplace_back();
    node_pool.emplace_back();
    root = &node_pool.back();
    first_leaf = &leaf_pool.back();
    first_leaf->parent = root;
    first_leaf->n = 1;
    first_leaf->e[0] = BEntry{UNDERWATER, UNDERWATER - 1, ROOT, ROOT, 1, false};
    root->leaf_children = true;
    root->n = 1;
    root->ch[0] = first_leaf;
    root->raw[0] = UNDERWATER - 1;
    root->cur[0] = UNDERWATER - 1;
    root->up[0] = UNDERWATER - 1;
    uw_index.set_range(UNDERWATER, UNDERWATER - 1, first_leaf);
  }

  inline void set_leaf(i64 ids, i64 len, BLeaf* lf) {
    if (ids < UNDERWATER) {
      assert(ids >= base && ids + len - base <= (i64)leaf_of.size());
      std::fill(leaf_of.begin() + (ids - base),
                leaf_of.begin() + (ids + len - base), lf);
    } else {
      uw_index.set_range(ids, len, lf);
    }
  }

  // ---- aggregate maintenance ----

  static inline void bump(BLeaf* lf, i64 draw, i64 dcur, i64 dup) {
    BNode* nd = lf->parent;
    int slot = lf->pslot;
    while (nd) {
      nd->raw[slot] += draw; nd->cur[slot] += dcur; nd->up[slot] += dup;
      slot = nd->pslot;
      nd = nd->parent;
    }
  }

  static void leaf_totals(const BLeaf* lf, i64& raw, i64& cur, i64& up) {
    raw = cur = up = 0;
    for (int i = 0; i < lf->n; i++) {
      raw += lf->e[i].len; cur += lf->e[i].cur(); up += lf->e[i].up();
    }
  }

  // ---- structure mutation ----

  void split_internal(BNode* nd) {
    while (nd->n == NODE_CAP) {
      node_pool.emplace_back();
      BNode* rn = &node_pool.back();
      int half = NODE_CAP / 2;
      rn->leaf_children = nd->leaf_children;
      rn->n = NODE_CAP - half;
      for (int i = 0; i < rn->n; i++) {
        rn->ch[i] = nd->ch[half + i];
        rn->raw[i] = nd->raw[half + i];
        rn->cur[i] = nd->cur[half + i];
        rn->up[i] = nd->up[half + i];
        if (rn->leaf_children) {
          ((BLeaf*)rn->ch[i])->parent = rn; ((BLeaf*)rn->ch[i])->pslot = i;
        } else {
          ((BNode*)rn->ch[i])->parent = rn; ((BNode*)rn->ch[i])->pslot = i;
        }
      }
      nd->n = half;
      i64 raw = 0, cur = 0, up = 0;
      for (int i = 0; i < rn->n; i++) {
        raw += rn->raw[i]; cur += rn->cur[i]; up += rn->up[i];
      }
      BNode* par = nd->parent;
      if (!par) {
        node_pool.emplace_back();
        BNode* nr = &node_pool.back();
        nr->leaf_children = false;
        nr->n = 2;
        i64 lraw = 0, lcur = 0, lup = 0;
        for (int i = 0; i < nd->n; i++) {
          lraw += nd->raw[i]; lcur += nd->cur[i]; lup += nd->up[i];
        }
        nr->ch[0] = nd; nr->raw[0] = lraw; nr->cur[0] = lcur; nr->up[0] = lup;
        nr->ch[1] = rn; nr->raw[1] = raw; nr->cur[1] = cur; nr->up[1] = up;
        nd->parent = nr; nd->pslot = 0;
        rn->parent = nr; rn->pslot = 1;
        root = nr;
        return;
      }
      int at = nd->pslot + 1;
      for (int i = par->n; i > at; i--) {
        par->ch[i] = par->ch[i - 1];
        par->raw[i] = par->raw[i - 1];
        par->cur[i] = par->cur[i - 1];
        par->up[i] = par->up[i - 1];
        ((BNode*)par->ch[i])->pslot = i;
      }
      par->ch[at] = rn;
      par->raw[at] = raw; par->cur[at] = cur; par->up[at] = up;
      par->raw[nd->pslot] -= raw; par->cur[nd->pslot] -= cur;
      par->up[nd->pslot] -= up;
      rn->parent = par; rn->pslot = at;
      par->n++;
      nd = par;
    }
  }

  // Split a full leaf; moved entries are re-registered in the space index.
  // Returns the new right leaf.
  BLeaf* split_leaf(BLeaf* lf) {
    leaf_pool.emplace_back();
    BLeaf* rn = &leaf_pool.back();
    int half = LEAF_CAP / 2;
    rn->n = LEAF_CAP - half;
    std::memcpy(rn->e, lf->e + half, rn->n * sizeof(BEntry));
    lf->n = half;
    rn->next = lf->next; if (rn->next) rn->next->prev = rn;
    rn->prev = lf; lf->next = rn;
    i64 raw, cur, up;
    leaf_totals(rn, raw, cur, up);
    BNode* par = lf->parent;
    if (par->n == NODE_CAP) { split_internal(par); par = lf->parent; }
    int at = lf->pslot + 1;
    for (int i = par->n; i > at; i--) {
      par->ch[i] = par->ch[i - 1];
      par->raw[i] = par->raw[i - 1];
      par->cur[i] = par->cur[i - 1];
      par->up[i] = par->up[i - 1];
      ((BLeaf*)par->ch[i])->pslot = i;
    }
    par->ch[at] = rn;
    par->raw[at] = raw; par->cur[at] = cur; par->up[at] = up;
    par->raw[lf->pslot] -= raw; par->cur[lf->pslot] -= cur;
    par->up[lf->pslot] -= up;
    rn->parent = par; rn->pslot = at;
    par->n++;
    // notify: moved entries now live in rn
    for (int i = 0; i < rn->n; i++)
      set_leaf(rn->e[i].ids, rn->e[i].len, rn);
    return rn;
  }

  // Insert `ent` at position (lf, at); returns the entry's new location.
  std::pair<BLeaf*, int> insert_entry(BLeaf* lf, int at, const BEntry& ent) {
    if (lf->n == LEAF_CAP) {
      BLeaf* rn = split_leaf(lf);
      if (at > lf->n) { at -= lf->n; lf = rn; }
    }
    for (int i = lf->n; i > at; i--) lf->e[i] = lf->e[i - 1];
    lf->e[at] = ent;
    lf->n++;
    bump(lf, ent.len, ent.cur(), ent.up());
    return {lf, at};
  }

  // Split entry (lf, idx) at offset `off` (0 < off < len). Returns the
  // location of the LEFT half; the right half sits at (leaf, idx+1) of the
  // returned location (guaranteed same leaf).
  std::pair<BLeaf*, int> split_entry(BLeaf* lf, int idx, i64 off) {
    BLeaf* orig = lf;
    BEntry right{lf->e[idx].ids + off, lf->e[idx].len - off,
                 lf->e[idx].ids + off - 1, lf->e[idx].orr,
                 lf->e[idx].state, lf->e[idx].ever};
    lf->e[idx].len = off;
    bump(lf, -right.len, -right.cur(), -right.up());
    if (lf->n == LEAF_CAP) {
      BLeaf* rn = split_leaf(lf);
      if (idx >= lf->n) { idx -= lf->n; lf = rn; }
    }
    for (int i = lf->n; i > idx + 1; i--) lf->e[i] = lf->e[i - 1];
    lf->e[idx + 1] = right;
    lf->n++;
    bump(lf, right.len, right.cur(), right.up());
    if (lf != orig) set_leaf(right.ids, right.len, lf);
    return {lf, idx};
  }

  // ---- lookup ----

  mutable BLeaf* hint_leaf = nullptr;
  mutable int hint_idx = 0;

  // (leaf, idx) of the entry containing lv
  std::pair<BLeaf*, int> ins_lookup(i64 lv) const {
    // LV ranges are globally disjoint, so a containment hit on the hint is
    // always the right entry; leaves live in a pool, so probing is safe.
    BLeaf* h = hint_leaf;
    if (h) {
      int i = hint_idx;
      if (i < h->n && h->e[i].ids <= lv && lv < h->e[i].ide()) return {h, i};
      if (i + 1 < h->n && h->e[i + 1].ids <= lv && lv < h->e[i + 1].ide()) {
        hint_idx = i + 1;
        return {h, i + 1};
      }
    }
    BLeaf* lf;
    if (lv < UNDERWATER) {
      assert(lv >= base && lv - base < (i64)leaf_of.size());
      lf = leaf_of[lv - base];
    } else {
      lf = uw_index.query(lv);
    }
    for (int i = 0; i < lf->n; i++)
      if (lf->e[i].ids <= lv && lv < lf->e[i].ide()) {
        hint_leaf = lf; hint_idx = i;
        return {lf, i};
      }
#ifdef DT_DEBUG_LOOKUP
    fprintf(stderr, "ins_lookup MISS lv=%lld mapped=%p\n", (long long)lv, (void*)lf);
    for (const BLeaf* sl = first_leaf; sl; sl = sl->next)
      for (int i = 0; i < sl->n; i++)
        if (sl->e[i].ids <= lv && lv < sl->e[i].ide()) {
          fprintf(stderr, "  actual leaf=%p idx=%d ids=%lld len=%lld\n",
                  (void*)sl, i, (long long)sl->e[i].ids, (long long)sl->e[i].len);
          abort();
        }
    fprintf(stderr, "  lv not in ANY leaf\n");
    abort();
#endif
    assert(false && "ins_lookup: lv not in mapped leaf");
    return {nullptr, 0};
  }

  // Returns the cursor for current-position pos; *up_out (optional) gets
  // the upstream-length prefix BEFORE the returned entry.
  Cursor find_by_cur(i64 pos, i64* up_out = nullptr) const {
    BNode* nd = root;
    i64 up = 0;
    while (true) {
      int i = 0;
      while (pos >= nd->cur[i]) {
        pos -= nd->cur[i]; up += nd->up[i]; i++;
        assert(i < nd->n);
      }
      if (nd->leaf_children) {
        BLeaf* lf = (BLeaf*)nd->ch[i];
        for (int j = 0; j < lf->n; j++) {
          i64 c = lf->e[j].cur();
          if (pos < c) { if (up_out) *up_out = up; return {lf, j, pos}; }
          pos -= c;
          up += lf->e[j].up();
        }
        assert(false && "find_by_cur: pos out of range");
      }
      nd = (BNode*)nd->ch[i];
    }
  }

  i64 prefix(const Cursor& c, int which) const {
    // which: 0 raw, 1 cur, 2 up
    i64 acc = 0;
    const BLeaf* lf = c.leaf;
    for (int i = 0; i < c.idx; i++) {
      const BEntry& e = lf->e[i];
      acc += which == 0 ? e.len : which == 1 ? e.cur() : e.up();
    }
    const BNode* nd = lf->parent;
    int slot = lf->pslot;
    while (nd) {
      const i64* agg = which == 0 ? nd->raw : which == 1 ? nd->cur : nd->up;
      for (int i = 0; i < slot; i++) acc += agg[i];
      slot = nd->pslot;
      nd = nd->parent;
    }
    return acc;
  }

  i64 total(int which) const {
    const i64* agg = which == 0 ? root->raw : which == 1 ? root->cur
                                            : root->up;
    i64 acc = 0;
    for (int i = 0; i < root->n; i++) acc += agg[i];
    return acc;
  }

  i64 raw_pos(const Cursor& c) const {
    if (!c.leaf) return total(0);
    return prefix(c, 0) + c.off;
  }

  i64 upstream_pos(const Cursor& c) const {
    if (!c.leaf) return total(2);
    return prefix(c, 2) + (c.leaf->e[c.idx].ever ? 0 : c.off);
  }

  // normalize so off < entry len; {nullptr} at end of doc
  bool roll(Cursor& c) const {
    if (!c.leaf) return false;
    while (c.off >= c.leaf->e[c.idx].len) {
      c.off -= c.leaf->e[c.idx].len;
      c.idx++;
      while (c.idx >= c.leaf->n) {
        if (!c.leaf->next) { c.leaf = nullptr; c.idx = 0; c.off = 0; return false; }
        c.leaf = c.leaf->next;
        c.idx = 0;
      }
    }
    return true;
  }

  // step to the next entry (ignores off)
  static bool next_entry(Cursor& c) {
    c.idx++; c.off = 0;
    while (c.idx >= c.leaf->n) {
      if (!c.leaf->next) { c.leaf = nullptr; c.idx = 0; return false; }
      c.leaf = c.leaf->next;
      c.idx = 0;
    }
    return true;
  }

  Cursor cursor_before_item(i64 lv) const {
    if (lv == ROOT) return {nullptr, 0, 0};  // end sentinel
    auto [lf, i] = ins_lookup(lv);
    return {lf, i, lv - lf->e[i].ids};
  }

  Cursor cursor_after_item(i64 lv) const {
    if (lv == ROOT) {
      BLeaf* lf = first_leaf;
      Cursor c{lf, 0, 0};
      roll(c);
      return c;
    }
    auto [lf, i] = ins_lookup(lv);
    Cursor c{lf, i, lv - lf->e[i].ids + 1};
    roll(c);
    return c;
  }

  int cmp_cursors(const Cursor& a, const Cursor& b) const {
    if (a.leaf == b.leaf) {
      if (a.idx != b.idx) return a.idx < b.idx ? -1 : 1;
      return a.off < b.off ? -1 : a.off > b.off ? 1 : 0;
    }
    i64 pa = raw_pos(a), pb = raw_pos(b);
    return pa < pb ? -1 : pa > pb ? 1 : 0;
  }

  // Try to RLE-merge entry (lf, idx) into its doc-order predecessor
  // (reference: YjsSpan::can_append, yjsspan.rs:168-174).
  void try_merge_left(BLeaf* lf, int idx) {
    BEntry& en = lf->e[idx];
    if (en.ol != en.ids - 1) return;
    if (idx > 0) {
      BEntry& pv = lf->e[idx - 1];
      if (pv.ide() != en.ids || pv.orr != en.orr ||
          pv.state != en.state || pv.ever != en.ever) return;
      pv.len += en.len;
      for (int i = idx; i < lf->n - 1; i++) lf->e[i] = lf->e[i + 1];
      lf->n--;
      // aggregates unchanged (same leaf, same totals); index unchanged.
    } else {
      BLeaf* pl = lf->prev;
      if (!pl || pl->n == 0 || lf->n <= 1) return;  // keep leaves non-empty
      BEntry& pv = pl->e[pl->n - 1];
      if (pv.ide() != en.ids || pv.orr != en.orr ||
          pv.state != en.state || pv.ever != en.ever) return;
      i64 raw = en.len, cur = en.cur(), up = en.up();
      pv.len += en.len;
      set_leaf(en.ids, en.len, pl);
      for (int i = 0; i < lf->n - 1; i++) lf->e[i] = lf->e[i + 1];
      lf->n--;
      bump(pl, raw, cur, up);
      bump(lf, -raw, -cur, -up);
    }
  }

  // Insert a new item entry at cursor position (splitting as needed).
  // Returns nothing; caller already computed positions.
  void insert_at(const Cursor& c, const BEntry& ent) {
    BLeaf* lf; int at;
    if (!c.leaf) {
      // end of doc: append after last entry of rightmost leaf
      BNode* nd = root;
      while (!nd->leaf_children) nd = (BNode*)nd->ch[nd->n - 1];
      lf = (BLeaf*)nd->ch[nd->n - 1];
      at = lf->n;
    } else if (c.off == 0) {
      lf = c.leaf; at = c.idx;
    } else if (c.off == c.leaf->e[c.idx].len) {
      lf = c.leaf; at = c.idx + 1;
    } else {
      auto [l2, i2] = split_entry(c.leaf, c.idx, c.off);
      lf = l2; at = i2 + 1;  // insert before the right half
    }
    // RLE append fast path: extend the left neighbor when the new item is
    // its linear continuation.
    BEntry* pv = nullptr;
    BLeaf* pvleaf = nullptr;
    if (at > 0) { pv = &lf->e[at - 1]; pvleaf = lf; }
    else if (lf->prev && lf->prev->n) {
      pvleaf = lf->prev; pv = &pvleaf->e[pvleaf->n - 1];
    }
    if (pv && ent.ol == ent.ids - 1 && pv->ide() == ent.ids &&
        pv->orr == ent.orr && pv->state == ent.state && pv->ever == ent.ever) {
      pv->len += ent.len;
      bump(pvleaf, ent.len, ent.cur(), ent.up());
      set_leaf(ent.ids, ent.len, pvleaf);
      return;
    }
    auto [l3, i3] = insert_entry(lf, at, ent);
    set_leaf(ent.ids, ent.len, l3);
  }

  // `up` is the upstream-length prefix before cursor's entry; threaded
  // through the scan so the final position needs no tree climb.
  i64 integrate(const Agents& aa, i64 agent, const BEntry& item,
                Cursor cursor, i64 up) {
    g_events.integrate_calls++;
    // roll, accumulating crossed entries into the upstream prefix
    auto roll_up = [&](Cursor& c) -> bool {
      if (!c.leaf) return false;
      while (c.off >= c.leaf->e[c.idx].len) {
        c.off -= c.leaf->e[c.idx].len;
        up += c.leaf->e[c.idx].up();
        c.idx++;
        while (c.idx >= c.leaf->n) {
          if (!c.leaf->next) { c.leaf = nullptr; c.idx = 0; c.off = 0; return false; }
          c.leaf = c.leaf->next;
          c.idx = 0;
        }
      }
      return true;
    };
    bool at_end = !roll_up(cursor);
    Cursor left_cursor = cursor;
    Cursor scan_start = cursor;
    i64 scan_up = up;
    bool scanning = false;

    while (!at_end && cursor.leaf) {
      g_events.integrate_scan_iters++;
      if (!roll_up(cursor)) break;
      const BEntry& other = cursor.leaf->e[cursor.idx];
      i64 off = cursor.off;
      i64 other_lv = other.ids + off;
      if (other_lv == item.orr) break;
      collisions++;
      assert(other.state == 0);

      i64 other_left_lv = other.origin_left_at(off);
      Cursor olc = cursor_after_item(other_left_lv);
      int c = cmp_cursors(olc, left_cursor);
      if (c < 0) break;
      if (c == 0) {
        if (item.orr == other.orr) {
          i64 oa, oseq;
          aa.local_to_agent(other_lv, oa, oseq);
          const std::string& my_name = aa.names[agent];
          const std::string& other_name = aa.names[oa];
          bool ins_here;
          if (my_name < other_name) ins_here = true;
          else if (my_name == other_name) {
            i64 ma, mseq;
            aa.local_to_agent(item.ids, ma, mseq);
            ins_here = mseq < oseq;
          } else ins_here = false;
          if (ins_here) break;
          scanning = false;
        } else {
          Cursor mr = cursor_before_item(item.orr);
          Cursor orc = cursor_before_item(other.orr);
          if (cmp_cursors(orc, mr) < 0) {
            if (!scanning) { scanning = true; scan_start = cursor; scan_up = up; }
          } else scanning = false;
        }
      }
      up += cursor.leaf->e[cursor.idx].up();
      if (!next_entry(cursor)) {
        cursor = {nullptr, 0, 0};
        break;
      }
    }
    if (scanning) { cursor = scan_start; up = scan_up; }
    Cursor at = cursor.leaf ? cursor : Cursor{nullptr, 0, 0};
    i64 pos;
    if (!at.leaf) pos = up;
    else pos = up + (at.leaf->e[at.idx].ever ? 0 : at.off);
    insert_at(at, item);
    return pos;
  }

  // returns (consumed, xf_pos) — xf_pos = -1 => delete already happened
  std::pair<i64, i64> apply(const Agents& aa, i64 agent, const OpRun& op,
                            i64 max_len) {
    i64 length = std::min(max_len, op.end - op.start);
    if (op.kind == INS) {
      del_cont_pos = -1;
      assert(op.fwd && "reverse insert runs unsupported");
      i64 origin_left;
      Cursor cursor;
      i64 up_prefix = 0;
      if (op.start == 0) {
        origin_left = ROOT;
        cursor = {first_leaf, 0, 0};
      } else {
        Cursor c = find_by_cur(op.start - 1, &up_prefix);
        origin_left = c.leaf->e[c.idx].ids + c.off;
        cursor = {c.leaf, c.idx, c.off + 1};
      }
      // origin_right: next non-NIY item at-or-after cursor
      Cursor c2 = cursor;
      i64 origin_right = ROOT;
      if (roll(c2)) {
        while (true) {
#ifdef DT_PROF
          extern long g_orr_iters;
          g_orr_iters++;
#endif
          const BEntry& e = c2.leaf->e[c2.idx];
          if (e.state == 0) {
            if (!next_entry(c2)) { origin_right = ROOT; break; }
          } else { origin_right = e.ids + c2.off; break; }
        }
      }
      BEntry item{op.lv, length, origin_left, origin_right, 1, false};
      i64 pos = integrate(aa, agent, item, cursor, up_prefix);
      return {length, pos};
    } else {
      bool fwd = op.fwd;
      Cursor cursor;
      i64 take_req;
      i64 up_prefix = 0;
      if (fwd) {
        if (op.start == del_cont_pos) {
          cursor = del_cont_cursor;
          up_prefix = del_cont_up;
        } else {
          cursor = find_by_cur(op.start, &up_prefix);
        }
        take_req = length;
      } else {
        i64 last_pos = op.end - 1;
        Cursor c = find_by_cur(last_pos, &up_prefix);
        i64 entry_start_pos = last_pos - c.off;
        i64 edit_start = std::max(entry_start_pos, op.end - length);
        take_req = op.end - edit_start;
        cursor = {c.leaf, c.idx, c.off - (take_req - 1)};
      }
      BLeaf* lf = cursor.leaf;
      int idx = cursor.idx;
      i64 off = cursor.off;
      assert(lf->e[idx].state == 1);
      bool ever_deleted = lf->e[idx].ever;
      i64 del_start_xf =
          up_prefix + (lf->e[idx].ever ? 0 : off);
      i64 take = std::min(take_req, lf->e[idx].len - off);
      if (off > 0) {
        auto [l2, i2] = split_entry(lf, idx, off);
        lf = l2; idx = i2 + 1;  // right half
      }
      if (take < lf->e[idx].len) {
        auto [l2, i2] = split_entry(lf, idx, take);
        lf = l2; idx = i2;  // left half
      }
      BEntry& en = lf->e[idx];
      i64 t0 = en.ids, t1 = en.ide();
      i64 dcur = en.state == 1 ? -(t1 - t0) : 0;
      i64 dup = en.ever ? 0 : -(t1 - t0);
      en.state += 1;
      en.ever = true;
      bump(lf, 0, dcur, dup);

      assert(op.lv >= base &&
             op.lv + take - base <= (i64)del_run_of.size());
      int32_t ri = (int32_t)del_list.size();
      del_list.push_back(DelRow{op.lv, op.lv + take, t0, t1, fwd});
      for (i64 v = op.lv; v < op.lv + take; v++) del_run_of[v - base] = ri;
      del_cont_pos = -1;
      if (fwd && take < take_req) {
        // roll to the next current entry for the continuation chunk,
        // folding crossed entries into the upstream prefix (left split
        // half contributes its pre-delete up(), the target now 0)
        i64 up2 = up_prefix + (ever_deleted ? 0 : off);
        Cursor c{lf, idx, 0};
        while (next_entry(c)) {
          const BEntry& ne = c.leaf->e[c.idx];
          if (ne.state == 1) break;
          up2 += ne.up();
        }
        if (c.leaf) {
          del_cont_cursor = c;
          del_cont_up = up2;
          del_cont_pos = op.start;
        }
      }
      return {take, ever_deleted ? -1 : del_start_xf};
    }
  }

  // ---- advance / retreat ----

  struct QueryRes { u8 kind; i64 t0, t1; bool fwd; i64 offset, total; };

  QueryRes index_query(i64 lv) const {
    assert(lv >= base && lv - base < (i64)del_run_of.size());
    if (del_run_of[lv - base] >= 0) {
      const DelRow& r = del_list[del_run_of[lv - base]];
      return {DEL, r.t0, r.t1, r.fwd, lv - r.lv0, r.lv1 - r.lv0};
    }
    auto [lf, i] = ins_lookup(lv);
    const BEntry& e = lf->e[i];
    return {INS, e.ids, e.ide(), true, lv - e.ids, e.len};
  }

  static void rr_sub(i64 t0, i64 t1, bool fwd, i64 o0, i64 o1,
                     i64& lo, i64& hi) {
    if (fwd) { lo = t0 + o0; hi = t0 + o1; }
    else { lo = t1 - o1; hi = t1 - o0; }
  }

  void toggle_items(i64 s, i64 e, int mode) {
    // modes: 0 ins, 1 unins, 2 del, 3 undel
    del_cont_pos = -1;
    i64 lv = s;
    while (lv < e) {
      auto [lf, idx] = ins_lookup(lv);
      if (lv > lf->e[idx].ids) {
        auto [l2, i2] = split_entry(lf, idx, lv - lf->e[idx].ids);
        lf = l2; idx = i2 + 1;  // right half
      }
      if (e < lf->e[idx].ide()) {
        auto [l2, i2] = split_entry(lf, idx, e - lf->e[idx].ids);
        lf = l2; idx = i2;  // left half
      }
      BEntry& en = lf->e[idx];
      i64 len = en.len;
      i64 dcur = 0, dup = 0;
      switch (mode) {
        case 0: assert(en.state == 0); en.state = 1; dcur = len; break;
        case 1: assert(en.state == 1); en.state = 0; dcur = -len; break;
        case 2:
          assert(en.state >= 1);
          if (en.state == 1) dcur = -len;
          en.state += 1;
          if (!en.ever) { dup = -len; en.ever = true; }
          break;
        case 3:
          assert(en.state >= 2);
          en.state -= 1;
          if (en.state == 1) dcur = len;
          break;
      }
      bump(lf, 0, dcur, dup);
      lv = en.ide();
      try_merge_left(lf, idx);
    }
  }

#ifdef DT_CHECK
  // Deep invariant checker (debug builds): parent aggregates vs recomputed
  // child totals, linked-list order, and index coverage of every entry.
  void check_node(BNode* nd) const {
    for (int i = 0; i < nd->n; i++) {
      if (nd->leaf_children) {
        BLeaf* lf = (BLeaf*)nd->ch[i];
        assert(lf->parent == nd && lf->pslot == i);
        assert(lf->n > 0);
        i64 raw, cur, up;
        leaf_totals(lf, raw, cur, up);
        assert(nd->raw[i] == raw && nd->cur[i] == cur && nd->up[i] == up);
      } else {
        BNode* c = (BNode*)nd->ch[i];
        assert(c->parent == nd && c->pslot == i);
        i64 raw = 0, cur = 0, up = 0;
        for (int j = 0; j < c->n; j++) {
          raw += c->raw[j]; cur += c->cur[j]; up += c->up[j];
        }
        assert(nd->raw[i] == raw && nd->cur[i] == cur && nd->up[i] == up);
        check_node(c);
      }
    }
  }
  void check() const {
    check_node(root);
    // every entry reachable via the linked list maps to its leaf
    for (BLeaf* lf = first_leaf; lf; lf = lf->next) {
      assert(lf->n > 0);
      for (int i = 0; i < lf->n; i++) {
        assert(lf->e[i].len > 0);
        if (lf->e[i].ids < UNDERWATER) {
          assert(leaf_of[lf->e[i].ids - base] == lf);
          assert(leaf_of[lf->e[i].ide() - 1 - base] == lf);
        } else {
          assert(uw_index.query(lf->e[i].ids) == lf);
          assert(uw_index.query(lf->e[i].ide() - 1) == lf);
        }
      }
    }
  }
#endif

  void advance_by_range(Span rng) {
    g_events.advance_calls++;
    i64 start = rng.start, end = rng.end;
    while (start < end) {
      QueryRes q = index_query(start);
      i64 take = std::min(q.total - q.offset, end - start);
      i64 lo, hi;
      rr_sub(q.t0, q.t1, q.fwd, q.offset, q.offset + take, lo, hi);
      toggle_items(lo, hi, q.kind == INS ? 0 : 2);
      start += take;
    }
  }

  void retreat_by_range(Span rng) {
    g_events.retreat_calls++;
    i64 start = rng.start, end = rng.end;
    while (start < end) {
      i64 req = end - 1;
      QueryRes q = index_query(req);
      i64 chunk_start = req - q.offset;
      i64 s = std::max(start, chunk_start);
      i64 e = std::min(end, chunk_start + q.total);
      i64 o0 = s - chunk_start;
      i64 lo, hi;
      rr_sub(q.t0, q.t1, q.fwd, o0, o0 + (e - s), lo, hi);
      toggle_items(lo, hi, q.kind == INS ? 1 : 3);
      end -= e - s;
    }
  }
};

#ifdef DT_PROF
#include <x86intrin.h>
struct ProfCounters {
  unsigned long long diff = 0, walk_fr = 0, retreat = 0, advance = 0,
                     apply_ins = 0, apply_del = 0, emit_misc = 0, doc = 0,
                     conflict = 0;
} g_prof;
struct ProfScope {
  unsigned long long* tgt;
  unsigned long long t0;
  ProfScope(unsigned long long* t) : tgt(t), t0(__rdtsc()) {}
  ~ProfScope() { *tgt += __rdtsc() - t0; }
};
#define PROF(field) ProfScope _ps(&g_prof.field)
extern "C" void dt_prof_dump() {
  fprintf(stderr,
          "prof cycles: diff=%llu walk_fr=%llu retreat=%llu advance=%llu "
          "apply_ins=%llu apply_del=%llu emit_misc=%llu doc=%llu "
          "conflict=%llu\n",
          g_prof.diff, g_prof.walk_fr, g_prof.retreat, g_prof.advance,
          g_prof.apply_ins, g_prof.apply_del, g_prof.emit_misc, g_prof.doc,
          g_prof.conflict);
  fprintf(stderr,
          "diff calls=%ld iters=%ld local_iters=%ld walk steps=%ld "
          "zero=%ld orr_iters=%ld\n",
          g_diff_calls, g_diff_iters, g_diff_iters2, g_walk_steps,
          g_walk_zero, g_orr_iters);
  g_orr_iters = 0;
  g_diff_calls = g_diff_iters = g_diff_iters2 = g_walk_steps = g_walk_zero = 0;
  g_prof = ProfCounters{};
}
#else
#define PROF(field)
extern "C" void dt_prof_dump() {}
#endif


// ---------------------------------------------------------------- walker
//
// Conflict-zone walker over a LOCAL piece graph (the listmerge2
// "conflict subgraph" idea, reference src/listmerge2/conflict_subgraph.rs,
// applied to the M1 pipeline): the conflict + new-op spans are chopped at
// graph-entry boundaries AND at every parent reference, so every frontier
// that can arise during the walk is exactly a set of piece-ends. Diffs then
// run over int32 piece indices with a small binary heap instead of heap
// walks over the global graph. Because each step's diff moves the frontier
// exactly onto the consumed piece's parents, the frontier after each
// consume is the single head {piece}, so no global frontier maintenance is
// needed inside the walk (reference equivalent: txn_trace.rs:75-160).

struct Piece {
  Span span;
  int32_t pstart, np;   // local parents slice into Zone::lpar
  u8 np_global;          // parent count incl. out-of-zone (walk heuristic)
  u8 phase;              // 0 = conflict (seed tracker), 1 = new ops (emit)
  bool visited = false;
};

struct Zone {
  std::vector<Piece> pieces;       // ascending LV order
  std::vector<int32_t> lpar;       // flat local parent idxs
  std::vector<int32_t> cindptr, cflat;  // children CSR
  std::vector<int32_t> pending;    // unvisited local parent count
  int32_t last_head = -1;          // last consumed piece (shared across phases)
  // scratch for diff_local: active bitmap + per-piece flag; each piece
  // enters the working set at most once (parents always have lower idx),
  // flags combine in place instead of queueing duplicates.
  std::vector<uint64_t> abits;
  std::vector<u8> aflag;
  std::vector<int32_t> touched;

  // a, b: descending span lists (phase 0 / phase 1)
  Zone(const Graph& g, const std::vector<Span>& conflict,
       const std::vector<Span>& fresh) {
    // 1. merge into ascending (span, phase) list
    struct SP { Span s; u8 phase; };
    std::vector<SP> spans;
    spans.reserve(conflict.size() + fresh.size());
    {
      auto ia = conflict.rbegin(), ea = conflict.rend();
      auto ib = fresh.rbegin(), eb = fresh.rend();
      while (ia != ea || ib != eb) {
        if (ib == eb || (ia != ea && ia->start < ib->start))
          spans.push_back({*ia++, 0});
        else
          spans.push_back({*ib++, 1});
      }
    }
    // 2. chop at graph entry boundaries -> proto piece spans. The graph
    //    entry index only moves forward across the ascending spans, so
    //    one binary search per span (not per entry) suffices.
    struct Proto { Span s; u8 phase; bool entry_head; uint32_t gi; };
    std::vector<Proto> protos;
    protos.reserve(spans.size() * 2);
    for (const SP& sp : spans) {
      i64 start = sp.s.start, end = sp.s.end;
      size_t i = g.find_idx(start);
      while (start < end) {
        i64 t_end = std::min(g.ends[i], end);
        protos.push_back({{start, t_end}, sp.phase, start == g.starts[i],
                          (uint32_t)i});
        start = t_end;
        i++;
      }
    }
    // 3. collect split points: every parent reference p with p+1 strictly
    //    inside a piece forces a boundary at p+1. Candidates are bounded
    //    LVs, so a bitmap gives dedup + sorted extraction for free (no
    //    sort/unique/merge-join); p+1 strictly inside a proto implies p
    //    is inside the same proto, so one containment form suffices.
    i64 lv_base = protos.empty() ? 0 : protos.front().s.start;
    i64 lv_top = protos.empty() ? 0 : protos.back().s.end;
    // biased by lv_base so the bitmap is O(zone extent), not O(history):
    // an incremental tail merge must not zero-fill the whole LV space
    std::vector<uint64_t> cutbits((size_t)(lv_top - lv_base + 64) / 64, 0);
    for (const Proto& pr : protos) {
      if (!pr.entry_head) continue;  // mid-entry pieces: single parent start-1
      for (size_t k = 0; k < g.pn(pr.gi); k++) {
        i64 c = g.pb(pr.gi)[k] + 1 - lv_base;
        if (c > 0 && c < lv_top - lv_base)
          cutbits[c >> 6] |= 1ull << (c & 63);
      }
    }
    std::vector<i64> cuts;
    for (const Proto& pr : protos) {
      i64 s = pr.s.start - lv_base, e = pr.s.end - lv_base;
      for (i64 w = s >> 6; w <= (e - 1) >> 6; w++) {
        uint64_t bits = cutbits[w];
        while (bits) {
          int b = __builtin_ctzll(bits);
          bits &= bits - 1;
          i64 c = (w << 6) | b;
          if (c > s && c < e) cuts.push_back(c + lv_base);
        }
      }
    }
    // 4. final pieces (pgi carries each piece's graph entry from step 2,
    //    phead whether it starts that entry — saves re-searching in 5)
    pieces.reserve(protos.size() + cuts.size());
    std::vector<uint32_t> pgi;
    std::vector<u8> phead;
    pgi.reserve(protos.size() + cuts.size());
    phead.reserve(protos.size() + cuts.size());
    size_t ci = 0;
    for (const Proto& pr : protos) {
      while (ci < cuts.size() && cuts[ci] <= pr.s.start) ci++;
      i64 start = pr.s.start;
      bool head = pr.entry_head;
      size_t cj = ci;
      while (start < pr.s.end) {
        i64 end = pr.s.end;
        if (cj < cuts.size() && cuts[cj] < end) end = cuts[cj++];
        Piece p;
        p.span = {start, end};
        p.phase = pr.phase;
        p.np_global = head ? 2 : 1;  // refined below for true heads
        p.pstart = 0; p.np = 0;
        pieces.push_back(p);
        pgi.push_back(pr.gi);
        phead.push_back(head ? 1 : 0);
        start = end;
        head = false;
      }
    }
    // 5. local parents. Every in-zone parent reference lands on a piece's
    //    last LV (that is what the cuts guarantee), so a linear-probe
    //    hash of span.end-1 -> piece idx answers each lookup O(1) — the
    //    old per-parent binary search was the constructor's hot spot.
    size_t hbits = 3;
    while ((1u << hbits) < pieces.size() * 2) hbits++;
    const size_t hmask = (1u << hbits) - 1;
    std::vector<i64> hkey(hmask + 1, -2);   // -2: empty (LVs are >= 0)
    std::vector<int32_t> hval(hmask + 1);
    auto hput = [&](i64 key, int32_t val) {
      size_t h = ((uint64_t)key * 0x9E3779B97F4A7C15ull) >> (64 - hbits);
      while (hkey[h] != -2) h = (h + 1) & hmask;
      hkey[h] = key; hval[h] = val;
    };
    auto hget = [&](i64 key) -> int32_t {
      size_t h = ((uint64_t)key * 0x9E3779B97F4A7C15ull) >> (64 - hbits);
      while (hkey[h] != -2) {
        if (hkey[h] == key) return hval[h];
        h = (h + 1) & hmask;
      }
      return -1;
    };
    for (size_t i = 0; i < pieces.size(); i++)
      hput(pieces[i].span.end - 1, (int32_t)i);
    for (size_t i = 0; i < pieces.size(); i++) {
      Piece& p = pieces[i];
      size_t gi = pgi[i];
      p.pstart = (int32_t)lpar.size();
      if (phead[i]) {
        p.np_global = (u8)std::min<size_t>(g.pn(gi), 255);
        for (size_t k = 0; k < g.pn(gi); k++) {
          int32_t pi = hget(g.pb(gi)[k]);
          if (pi >= 0) lpar.push_back(pi);
        }
      } else {
        p.np_global = 1;
        int32_t pi = hget(p.span.start - 1);
        if (pi >= 0) lpar.push_back(pi);
      }
      p.np = (int32_t)(lpar.size() - p.pstart);
    }
    // 6. children CSR + pending counters
    cindptr.assign(pieces.size() + 1, 0);
    for (int32_t pi : lpar) cindptr[pi + 1]++;
    for (size_t i = 0; i < pieces.size(); i++) cindptr[i + 1] += cindptr[i];
    cflat.resize(lpar.size());
    {
      std::vector<int32_t> fill(cindptr.begin(), cindptr.end() - 1);
      for (size_t i = 0; i < pieces.size(); i++)
        for (int32_t k = 0; k < pieces[i].np; k++)
          cflat[fill[lpar[pieces[i].pstart + k]]++] = (int32_t)i;
    }
    pending.resize(pieces.size());
    for (size_t i = 0; i < pieces.size(); i++) pending[i] = pieces[i].np;
    abits.assign((pieces.size() + 63) / 64, 0);
    aflag.assign(pieces.size(), 0);
  }

  // diff between head closure and parents closure, over local idxs.
  // Appends descending piece idxs to retreat (head-only) / advance
  // (parents-only).
  void diff_local(int32_t head, const int32_t* par, int32_t np,
                  std::vector<int32_t>& retreat_i,
                  std::vector<int32_t>& advance_i) {
    enum : u8 { A = 0, B = 1, Shared = 2 };
    g_events.walk_steps++;
#ifdef DT_PROF
    extern long g_walk_steps, g_walk_zero, g_diff_iters2;
    g_walk_steps++;
    if (np == 1 && par[0] == head) g_walk_zero++;
#endif
    if (np == 1 && par[0] == head) return;  // zero-churn chain step
    int hi_word = -1;
    long nonshared = 0;
    touched.clear();
    auto bit_push = [&](int32_t idx, u8 flag) {
      int w = idx >> 6;
      uint64_t m = 1ull << (idx & 63);
      if (abits[w] & m) {
        u8 old = aflag[idx];
        if (old != Shared && old != flag) { aflag[idx] = Shared; nonshared--; }
      } else {
        abits[w] |= m;
        aflag[idx] = flag;
        touched.push_back(idx);
        if (flag != Shared) nonshared++;
        if (w > hi_word) hi_word = w;
      }
    };
    if (head >= 0) bit_push(head, A);
    for (int32_t k = 0; k < np; k++) bit_push(par[k], B);
    while (nonshared > 0) {
#ifdef DT_PROF
      g_diff_iters2++;
#endif
      while (abits[hi_word] == 0) hi_word--;
      int b = 63 - __builtin_clzll(abits[hi_word]);
      int32_t idx = (int32_t)((hi_word << 6) | b);
      abits[hi_word] &= ~(1ull << b);
      u8 flag = aflag[idx];
      if (flag != Shared) nonshared--;
      if (flag == A) retreat_i.push_back(idx);
      else if (flag == B) advance_i.push_back(idx);
      const Piece& p = pieces[idx];
      for (int32_t k = 0; k < p.np; k++) bit_push(lpar[p.pstart + k], flag);
    }
    // clear any bits left set by the early (all-Shared) exit
    for (int32_t idx : touched) abits[idx >> 6] &= ~(1ull << (idx & 63));
  }
};

struct Walker {
  Zone& z;
  u8 phase;
  std::vector<int32_t> to_process;
  std::vector<int32_t> retreat_i, advance_i;

  Walker(Zone& zone, u8 ph) : z(zone), phase(ph) {
    for (int i = (int)z.pieces.size() - 1; i >= 0; i--)
      if (z.pieces[i].phase == phase && !z.pieces[i].visited &&
          z.pending[i] == 0)
        to_process.push_back(i);
  }

  // returns false when done
  bool next(std::vector<Span>& retreat, std::vector<Span>& advance_rev,
            Span& consume) {
    if (to_process.empty()) return false;
    // reference heuristic (txn_trace.rs:240-258): defer merge pieces,
    // preferring the most recently readied non-merge piece
    int32_t idx = to_process.back();
    if (z.pieces[idx].np_global >= 2) {
      int found = -1;
      for (int ii = (int)to_process.size() - 1; ii >= 0; ii--) {
        if (z.pieces[to_process[ii]].np_global < 2) { found = ii; break; }
      }
      if (found >= 0) {
        idx = to_process[found];
        to_process[found] = to_process.back();
        to_process.pop_back();
      } else to_process.pop_back();
    } else to_process.pop_back();

    Piece& e = z.pieces[idx];
    e.visited = true;

    retreat.clear(); advance_rev.clear();
    { PROF(diff);
      retreat_i.clear(); advance_i.clear();
      z.diff_local(z.last_head, z.lpar.data() + e.pstart, e.np,
                   retreat_i, advance_i);
      for (int32_t i : retreat_i)
        push_reversed_rle(retreat, z.pieces[i].span);
      for (int32_t i : advance_i)
        push_reversed_rle(advance_rev, z.pieces[i].span);
    }
    z.last_head = idx;

    for (int32_t k = z.cindptr[idx]; k < z.cindptr[idx + 1]; k++) {
      int32_t c = z.cflat[k];
      if (--z.pending[c] == 0 && z.pieces[c].phase == phase)
        to_process.push_back(c);
    }
    consume = e.span;
    // Zero-churn chain coalescing: while the piece just readied is idx's
    // sole-parent successor with an LV-contiguous span (an entry run the
    // cut pass split, or a straight chain), fold it into this consume —
    // its diff would be empty and its frontier is just {predecessor}, so
    // skipping the per-piece scaffolding (diff, emit lookup, graph
    // advance) changes nothing observable.
    while (!to_process.empty()) {
      int32_t c = to_process.back();
      Piece& pc = z.pieces[c];
      if (pc.np != 1 || z.lpar[pc.pstart] != idx ||
          pc.span.start != consume.end)
        break;
      to_process.pop_back();
      pc.visited = true;
      g_events.walk_steps++;
      consume.end = pc.span.end;
      idx = c;
      z.last_head = c;
      for (int32_t k = z.cindptr[c]; k < z.cindptr[c + 1]; k++) {
        int32_t cc = z.cflat[k];
        if (--z.pending[cc] == 0 && z.pieces[cc].phase == phase)
          to_process.push_back(cc);
      }
    }
    return true;
  }
};

// ---------------------------------------------------------------- context

struct XfOp { i64 lv; i64 len; u8 kind; u8 fwd; i64 pos; };  // pos=-1 => gone

// Chunked int32 text buffer (the native rope; mirrors
// diamond_types_tpu/utils/rope.py).
struct TextBuf {
  static const size_t TARGET = 2048;
  static const size_t GROUP = 64;  // chunks per group-sum slot
  std::vector<std::vector<int32_t>> chunks;
  std::vector<i64> sizes;  // parallel to chunks
  std::vector<i64> gsum;   // per-group char totals (incremental index)
  i64 total = 0;

  TextBuf() { chunks.emplace_back(); sizes.push_back(0); gsum.push_back(0); }

  // O(#chunks); only needed when chunks are added/removed (split, erase)
  void rebuild_groups() {
    gsum.assign((chunks.size() + GROUP - 1) / GROUP, 0);
    for (size_t i = 0; i < chunks.size(); i++) gsum[i / GROUP] += sizes[i];
  }

  std::pair<size_t, i64> find(i64 pos) const {
    size_t g = 0;
    while (g + 1 < gsum.size() && pos >= gsum[g]) { pos -= gsum[g]; g++; }
    size_t i = g * GROUP;
    size_t end = std::min(chunks.size(), (g + 1) * GROUP);
    while (i + 1 < end && pos >= sizes[i]) { pos -= sizes[i]; i++; }
    return {i, pos};
  }

  void insert(i64 pos, const int32_t* s, i64 n) {
    if (n <= 0) return;
    auto [ci, off] = find(pos);
    auto& ch = chunks[ci];
    ch.insert(ch.begin() + off, s, s + n);
    sizes[ci] += n;
    gsum[ci / GROUP] += n;
    total += n;
    if (ch.size() > 2 * TARGET) {
      // split into TARGET-sized chunks
      std::vector<std::vector<int32_t>> parts;
      for (size_t i = 0; i < ch.size(); i += TARGET)
        parts.emplace_back(ch.begin() + i,
                           ch.begin() + std::min(ch.size(), i + TARGET));
      chunks.erase(chunks.begin() + ci);
      sizes.erase(sizes.begin() + ci);
      sizes.insert(sizes.begin() + ci, parts.size(), 0);
      for (size_t i = 0; i < parts.size(); i++)
        sizes[ci + i] = (i64)parts[i].size();
      chunks.insert(chunks.begin() + ci,
                    std::make_move_iterator(parts.begin()),
                    std::make_move_iterator(parts.end()));
      rebuild_groups();
    }
  }

  void erase(i64 pos, i64 n) {
    if (n <= 0) return;
    total -= n;
    auto [ci, off] = find(pos);
    bool removed = false;
    while (n > 0) {
      auto& ch = chunks[ci];
      i64 take = std::min((i64)ch.size() - off, n);
      ch.erase(ch.begin() + off, ch.begin() + off + take);
      sizes[ci] -= take;
      if (!removed) gsum[ci / GROUP] -= take;
      n -= take;
      if (ch.empty() && chunks.size() > 1) {
        chunks.erase(chunks.begin() + ci);
        sizes.erase(sizes.begin() + ci);
        removed = true;
      } else {
        ci++;
      }
      off = 0;
    }
    if (removed) rebuild_groups();
  }

  void dump(int32_t* out) const {
    i64 k = 0;
    for (const auto& ch : chunks) {
      std::memcpy(out + k, ch.data(), ch.size() * sizeof(int32_t));
      k += ch.size();
    }
  }
};

// ------------------------------------------------------------- composer
//
// Piece-table composer for the zone engine's host prep: composes one
// conflict-zone entry's sequential op stream into entry-start coordinates
// (a faithful port of diamond_types_tpu/listmerge/compose.py — see that
// module's docstring for the semantics; reference equivalent of the work
// it replaces: the per-op tracker origin scan, src/listmerge/merge.rs:
// 395-423). Treap over piece nodes in an index arena; the tree SHAPE may
// differ from the Python treap (priorities are independent randomness)
// but the in-order piece sequence — the only thing finish() reads — is
// identical.

using u64 = unsigned long long;
using u32 = unsigned int;

static const i64 COMP_BASE_INF = (i64)1 << 40;
static const u8 COMP_K_OWN = 1, COMP_K_LEFTJOIN = 2, COMP_K_ROOT = 3;

struct CompPiece {
  i64 base;      // >= 0: snapshot chars [base, base+length); -1: own chars
  i64 lv;        // own chars [lv, lv+length)
  i64 length;
  int headi;     // own: index into Composer::heads (governing run head)
  u64 prio;
  int l, r, up;
  i64 sub_alive;
  bool alive;
};

struct CompHead {
  u8 kind;        // COMP_K_*
  i64 anchor_lv;  // own-char anchor (K_OWN parent / K_LEFTJOIN parent)
  int q;          // query idx (K_LEFTJOIN ol / K_ROOT), else -1
  int block;      // block id the run belongs to
  i64 orr_own;    // own-char origin-right lv, or -1 = the block's B
  i64 head_lv;    // the run head char's own lv
};

// One entry's composition result (mirror of compose.ComposedEntry).
struct ComposedOut {
  std::vector<i64> q_cursor;
  std::vector<i64> ch_lv, ch_anchor, ch_headlv, ch_orrown;
  std::vector<int32_t> ch_block, ch_q;
  std::vector<u8> ch_head, ch_kind;
  std::vector<int32_t> blk_root_q, blk_start, blk_len;
  std::vector<i64> blk_root_lv;
  std::vector<i64> db0, db1, do0, do1;  // del_base / del_own pairs
};

struct Composer {
  std::vector<CompPiece> A;
  std::vector<CompHead> heads;
  int root = -1;
  u64 prio_state = 0x9E3779B97F4A7C15ull;
  std::vector<i64> q_cursor;
  int n_blocks = 0;
  std::vector<i64> blk_root_lv_all;   // block id -> root head char lv
  std::vector<int> blk_root_headi;    // block id -> root head meta idx
  std::vector<std::pair<i64, i64>> del_base, del_own;
  bool failed = false;

  Composer(bool with_base) {
    if (with_base) {
      A.push_back({0, -1, COMP_BASE_INF, -1, next_prio(), -1, -1, -1,
                   COMP_BASE_INF, true});
      root = 0;
    }
  }

  u64 next_prio() {   // splitmix64
    prio_state += 0x9E3779B97F4A7C15ull;
    u64 z = prio_state;
    z = (z ^ (z >> 30)) * 0xBF58476D1CE4E5B9ull;
    z = (z ^ (z >> 27)) * 0x94D049BB133111EBull;
    return z ^ (z >> 31);
  }

  inline void upd(int n) {
    CompPiece& p = A[n];
    i64 s = p.alive ? p.length : 0;
    if (p.l >= 0) s += A[p.l].sub_alive;
    if (p.r >= 0) s += A[p.r].sub_alive;
    p.sub_alive = s;
  }

  void fix_up(int n) { while (n >= 0) { upd(n); n = A[n].up; } }

  void rot_up(int x) {
    int p = A[x].up, g = A[p].up;
    if (A[p].l == x) {
      A[p].l = A[x].r;
      if (A[p].l >= 0) A[A[p].l].up = p;
      A[x].r = p;
    } else {
      A[p].r = A[x].l;
      if (A[p].r >= 0) A[A[p].r].up = p;
      A[x].l = p;
    }
    A[p].up = x;
    A[x].up = g;
    if (g >= 0) { if (A[g].l == p) A[g].l = x; else A[g].r = x; }
    else root = x;
    upd(p);
    upd(x);
  }

  void bubble(int x) {
    while (A[x].up >= 0 && A[A[x].up].prio < A[x].prio) rot_up(x);
    if (A[x].up < 0) root = x; else fix_up(A[x].up);
  }

  void insert_after(int a, int x) {
    if (a < 0) {
      int n = root;
      if (n < 0) { root = x; return; }
      while (A[n].l >= 0) n = A[n].l;
      A[n].l = x;
      A[x].up = n;
    } else if (A[a].r < 0) {
      A[a].r = x;
      A[x].up = a;
    } else {
      int n = A[a].r;
      while (A[n].l >= 0) n = A[n].l;
      A[n].l = x;
      A[x].up = n;
    }
    fix_up(A[x].up);
    bubble(x);
  }

  int succ(int n) const {
    if (A[n].r >= 0) {
      n = A[n].r;
      while (A[n].l >= 0) n = A[n].l;
      return n;
    }
    while (A[n].up >= 0 && A[A[n].up].r == n) n = A[n].up;
    return A[n].up;
  }

  int leftmost() const {
    int n = root;
    if (n < 0) return -1;
    while (A[n].l >= 0) n = A[n].l;
    return n;
  }

  // (piece, offset) of visible char pos; piece < 0 on out-of-range
  std::pair<int, i64> find_visible(i64 pos) const {
    int n = root;
    while (n >= 0) {
      const CompPiece& p = A[n];
      i64 la = p.l >= 0 ? A[p.l].sub_alive : 0;
      if (pos < la) n = p.l;
      else if (p.alive && pos < la + p.length) return {n, pos - la};
      else { pos -= la + (p.alive ? p.length : 0); n = p.r; }
    }
    return {-1, 0};
  }

  int split(int n, i64 off) {
    int right;
    CompPiece& p0 = A[n];
    if (p0.base >= 0)
      A.push_back({p0.base + off, -1, p0.length - off, -1, next_prio(),
                   -1, -1, -1, 0, p0.alive});
    else
      A.push_back({-1, p0.lv + off, p0.length - off, p0.headi, next_prio(),
                   -1, -1, -1, 0, p0.alive});
    right = (int)A.size() - 1;
    A[right].sub_alive = A[right].alive ? A[right].length : 0;
    A[n].length = off;
    fix_up(n);
    insert_after(n, right);
    return right;
  }

  int emit_query(int prev) {
    // query gap must follow a snapshot piece (or doc start)
    if (prev >= 0 && A[prev].base < 0) { failed = true; return -1; }
    q_cursor.push_back(prev < 0 ? 0 : A[prev].base + A[prev].length);
    return (int)q_cursor.size() - 1;
  }

  void insert(i64 pos, i64 lv, i64 length) {
    int prev;
    if (pos == 0) prev = -1;
    else {
      auto [node, off] = find_visible(pos - 1);
      if (node < 0) { failed = true; return; }
      if (off + 1 < A[node].length) split(node, off + 1);
      prev = node;
    }
    int nxt = prev >= 0 ? succ(prev) : leftmost();
    i64 orr_own = (nxt >= 0 && A[nxt].base < 0) ? A[nxt].lv : -1;
    int headi = (int)heads.size();
    if (prev >= 0 && A[prev].base < 0) {
      // ol is an own char: right child of it (K_OWN)
      i64 anchor = A[prev].lv + A[prev].length - 1;
      heads.push_back({COMP_K_OWN, anchor, -1, heads[A[prev].headi].block,
                       orr_own, lv});
    } else if (nxt >= 0 && A[nxt].base < 0) {
      // ol snapshot/doc-start, next piece own: left-join that block
      int q = emit_query(prev);
      heads.push_back({COMP_K_LEFTJOIN, A[nxt].lv, q,
                       heads[A[nxt].headi].block, orr_own, lv});
    } else {
      int q = emit_query(prev);
      int blk = n_blocks++;
      blk_root_lv_all.push_back(lv);
      blk_root_headi.push_back(headi);
      heads.push_back({COMP_K_ROOT, -1, q, blk, -1, lv});
    }
    A.push_back({-1, lv, length, headi, next_prio(), -1, -1, -1,
                 length, true});
    insert_after(prev, (int)A.size() - 1);
  }

  void del(i64 pos, i64 length) {
    auto [node, off] = find_visible(pos);
    if (node < 0) { failed = true; return; }
    if (off > 0) node = split(node, off);
    i64 remaining = length;
    while (remaining > 0) {
      if (node < 0) { failed = true; return; }  // delete past end
      if (!A[node].alive) { node = succ(node); continue; }
      i64 take = std::min(remaining, A[node].length);
      if (take < A[node].length) split(node, take);
      if (A[node].base >= 0)
        del_base.emplace_back(A[node].base, A[node].base + take);
      else
        del_own.emplace_back(A[node].lv, A[node].lv + take);
      A[node].alive = false;
      fix_up(node);
      remaining -= take;
      node = succ(node);
    }
  }

  void finish(ComposedOut& out) {
    out.q_cursor = std::move(q_cursor);
    for (auto& d : del_base) { out.db0.push_back(d.first);
                               out.db1.push_back(d.second); }
    for (auto& d : del_own)  { out.do0.push_back(d.first);
                               out.do1.push_back(d.second); }
    // in-order walk collecting own pieces grouped by block id;
    // intra-block order IS table order
    struct PBRow { i64 lv, len; int headi; };
    std::vector<std::vector<PBRow>> pb(n_blocks);
    {
      std::vector<int> st;
      int cur = root;
      while (!st.empty() || cur >= 0) {
        while (cur >= 0) { st.push_back(cur); cur = A[cur].l; }
        cur = st.back();
        st.pop_back();
        const CompPiece& p = A[cur];
        if (p.base < 0)
          pb[heads[p.headi].block].push_back({p.lv, p.length, p.headi});
        cur = p.r;
      }
    }
    for (int blk = 0; blk < n_blocks; blk++) {
      if (pb[blk].empty()) continue;   // dense output block reindex
      int bi = (int)out.blk_start.size();
      i64 total = out.ch_lv.size();
      i64 blen = 0;
      for (auto& t : pb[blk]) blen += t.len;
      out.blk_start.push_back((int32_t)total);
      out.blk_len.push_back((int32_t)blen);
      out.blk_root_lv.push_back(blk_root_lv_all[blk]);
      out.blk_root_q.push_back(heads[blk_root_headi[blk]].q);
      for (auto& t : pb[blk]) {
        i64 lv = t.lv, ln = t.len;
        const CompHead& h = heads[t.headi];
        for (i64 k = 0; k < ln; k++) {
          i64 clv = lv + k;
          bool is_head = clv == h.head_lv;
          out.ch_lv.push_back(clv);
          out.ch_block.push_back(bi);
          out.ch_headlv.push_back(h.head_lv);
          out.ch_orrown.push_back(h.orr_own);
          out.ch_head.push_back(is_head ? 1 : 0);
          out.ch_kind.push_back(is_head ? h.kind : 0);
          out.ch_anchor.push_back(is_head ? h.anchor_lv : -1);
          out.ch_q.push_back(is_head ? h.q : -1);
        }
      }
    }
  }
};

namespace zonepack {

struct Step {
  int32_t op, a, b, snap;
  std::vector<std::array<int32_t, 5>> blocks;  // cursor, prev, root, start, len
  std::vector<std::array<int32_t, 7>> chars;   // slot, ol_s, ol_c, orr, blk, ag, sq
  std::vector<std::array<int32_t, 3>> dels;    // kind, a, b
};

struct PackState {
  std::vector<Step> steps;
  i64 MB, MC, MD;
  Step* cur = nullptr;

  Step* new_step(int32_t op, int32_t a, int32_t b, int32_t snap) {
    steps.push_back(Step{op, a, b, snap, {}, {}, {}});
    cur = &steps.back();
    return cur;
  }
};

}  // namespace zonepack

struct Ctx {
  Graph g;
  Agents aa;
  Ops ops;
  std::vector<int32_t> ins_arena;
  TextBuf doc;
  std::vector<i64> version;
  std::vector<XfOp> out;
  std::vector<i64> out_frontier;
  // kept after transform for dt_dump_tracker (device-linearizer oracle)
  std::unique_ptr<Tracker> last_tracker;
  // conflict zone's common-ancestor frontier (the version whose document
  // the tracker's underwater id space tiles)
  std::vector<i64> zone_common;
  // collisions of the LAST transform (survives release_tracker)
  i64 last_collisions = 0;
  // dt_zone_pack's two-call fetch buffer
  std::vector<zonepack::Step> pack_steps;
  // compose-cache identity: bumped by every dt_compose_plan; the packer
  // validates it so a cache from a DIFFERENT plan (same entry count)
  // can never be packed silently
  i64 compose_serial = 0;
  // dt_merge_into_doc's zone-everything mode (from=[] merging onto an
  // empty doc): transform skips FF so the WHOLE history walks the zone
  // and the final doc assembles straight from the tracker in one leaf
  // pass — no per-op rope surgery, no out-row recording. FF's
  // untransformed emission and the tracker walk produce the same
  // document; this trades a little extra integrate work on the linear
  // prefix (tiny on the shipped corpora) for dropping the rope phase.
  bool merge_no_ff = false;
  // last dt_compose_plan / dt_compose_linear results
  std::vector<ComposedOut> composed;
  std::vector<std::pair<i64, i64>> linear_pieces;
  // transform() metadata for dt_merge_into_doc's fast doc assembly:
  // out[0..ff_split) are the FF-mode untransformed ops; zone_ff_base is
  // true when the conflict zone's phase-0 seed set was empty (every
  // forward merge / checkout), i.e. the underwater id space tiles
  // exactly the rope state after the FF ops.
  size_t ff_split = 0;
  bool zone_ff_base = false;
  // last dt_encode_full result
  std::vector<u8> enc_buf;
};

// Feed one span's op runs through a composer (mirror of
// compose.compose_entry's iter_range loop). False on unsupported input
// (reverse insert runs — matches reference merge.rs:384 unimplemented!).
static bool compose_span_ops(Ctx* c, Composer& comp, Span span) {
  Ops& ops = c->ops;
  if (span_empty(span)) return true;
  size_t i = ops.find_idx(span.start);
  i64 pos = span.start;
  while (pos < span.end) {
    const OpRun& run = ops.runs[i];
    i64 run_end = run.lv + (run.end - run.start);
    i64 o0 = pos - run.lv;
    i64 o1 = std::min(span.end, run_end) - run.lv;
    OpRun piece = Ops::slice(run, o0, o1);
    i64 plen = piece.end - piece.start;
    if (piece.kind == INS) {
      if (!piece.fwd) return false;
      comp.insert(piece.start, piece.lv, plen);
    } else {
      comp.del(piece.start, plen);
    }
    if (comp.failed) return false;
    pos = run.lv + o1;
    i++;
  }
  return true;
}

static void emit_ops_range(Ctx* c, Tracker& tracker, Span consume,
                           bool emit) {
  Ops& ops = c->ops;
  if (span_empty(consume)) return;
  size_t i = ops.find_idx(consume.start);
  i64 pos = consume.start;
  while (pos < consume.end) {
    const OpRun& run = ops.runs[i];
    i64 run_end = run.lv + (run.end - run.start);
    i64 o0 = pos - run.lv;
    i64 o1 = std::min(consume.end, run_end) - run.lv;
    OpRun piece = Ops::slice(run, o0, o1);
    // apply in chunks bounded by agent runs; the agent lookup is hoisted
    // across entry-bounded chunks of the same run (alen counts down)
    i64 agent = -1, alen = 0;
    while (true) {
      i64 plen = piece.end - piece.start;
      if (alen <= 0) {
        i64 seq;
        c->aa.local_to_agent(piece.lv, agent, seq);
        alen = c->aa.span_len(piece.lv, plen);
      }
      std::pair<i64,i64> r;
      if (piece.kind == INS) { PROF(apply_ins); g_events.apply_ins_runs++; r = tracker.apply(c->aa, agent, piece, alen); }
      else { PROF(apply_del); g_events.apply_del_runs++; r = tracker.apply(c->aa, agent, piece, alen); }
      auto [consumed, xf] = r;
#ifdef DT_CHECK
      fprintf(stderr, "applied lv=%lld len=%lld kind=%d\n",
              (long long)piece.lv, (long long)consumed, (int)piece.kind);
      tracker.check();
#endif
      if (emit && !c->merge_no_ff)
        c->out.push_back({piece.lv, consumed, piece.kind, piece.fwd, xf});
      alen -= consumed;
      if (consumed == plen) break;
      piece = Ops::slice(piece, consumed, plen);
    }
    pos = run.lv + o1;
    i++;
  }
}

static void transform(Ctx* c, std::vector<i64> from, std::vector<i64> merge) {
  c->out.clear();
  c->last_tracker.reset();
  c->last_collisions = 0;
  c->ff_split = 0;
  c->zone_ff_base = false;
  std::vector<Span> new_ops, conflict_ops;
  { PROF(conflict);
    if (from.empty() && merge == c->g.heads) {
      // trivial checkout (the complex/merge bench shape): everything
      // reachable from the full frontier is OnlyB in one span — skip
      // the whole heap walk
      if (!c->g.ends.empty()) new_ops.push_back({0, c->g.ends.back()});
      c->zone_common.clear();
    } else {
      c->zone_common = c->g.find_conflicting(
          from, merge, [&](Span s, u8 flag) {
            push_reversed_rle(flag == Graph::OnlyB ? new_ops : conflict_ops,
                              s);
          });
    }
  }

  std::vector<i64> next_frontier = from;
  bool did_ff = false;

  // FF mode
  std::vector<i64> ps;
  while (!c->merge_no_ff && !new_ops.empty()) {
    Span span = new_ops.back();
    size_t i = c->g.find_idx(span.start);
    c->g.parents_at(span.start, ps);
    if (ps != next_frontier) break;
    new_ops.pop_back();
    i64 take_end = std::min(c->g.ends[i], span.end);
    if (take_end < span.end) new_ops.push_back({take_end, span.end});
    next_frontier.assign(1, take_end - 1);
    did_ff = true;
    // emit untransformed
    Ops& ops = c->ops;
    size_t oi = ops.find_idx(span.start);
    i64 pos = span.start;
    while (pos < take_end) {
      const OpRun& run = ops.runs[oi];
      i64 run_end = run.lv + (run.end - run.start);
      i64 o1 = std::min(take_end, run_end) - run.lv;
      OpRun piece = Ops::slice(run, pos - run.lv, o1);
      c->out.push_back({piece.lv, piece.end - piece.start, piece.kind,
                        piece.fwd, piece.start});
      pos = run.lv + o1;
      oi++;
    }
  }

  c->ff_split = c->out.size();
  if (!new_ops.empty()) {
    if (did_ff) {
      conflict_ops.clear();
      c->zone_common = c->g.find_conflicting(
          next_frontier, merge, [&](Span s, u8 flag) {
            if (flag != Graph::OnlyB) push_reversed_rle(conflict_ops, s);
          });
    }
    c->zone_ff_base = conflict_ops.empty();

    i64 ops_top = 0;
    if (!c->ops.runs.empty()) {
      const OpRun& lr = c->ops.runs.back();
      ops_top = lr.lv + (lr.end - lr.start);
    }
    i64 zone_base = ops_top;
    for (const Span& s : conflict_ops) zone_base = std::min(zone_base, s.start);
    for (const Span& s : new_ops) zone_base = std::min(zone_base, s.start);
    c->last_tracker.reset(new Tracker(zone_base, ops_top));
    Tracker& tracker = *c->last_tracker;
    std::unique_ptr<Zone> zp;
    { PROF(emit_misc); zp.reset(new Zone(c->g, conflict_ops, new_ops)); }
    Zone& zone = *zp;
    // build tracker over conflict set
    {
      Walker w(zone, 0);
      std::vector<Span> retreat, advance_rev;
      Span consume;
      while (w.next(retreat, advance_rev, consume)) {
        { PROF(retreat);
          for (const Span& s : retreat) tracker.retreat_by_range(s); }
        { PROF(advance);
          for (auto it = advance_rev.rbegin(); it != advance_rev.rend(); ++it)
            tracker.advance_by_range(*it); }
        emit_ops_range(c, tracker, consume, false);
      }
      // walk new ops
      Walker w2(zone, 1);
      while (w2.next(retreat, advance_rev, consume)) {
        { PROF(retreat);
          for (const Span& s : retreat) tracker.retreat_by_range(s); }
        { PROF(advance);
          for (auto it = advance_rev.rbegin(); it != advance_rev.rend(); ++it)
            tracker.advance_by_range(*it); }
        c->g.advance(next_frontier, consume);
        emit_ops_range(c, tracker, consume, true);
      }
    }
    c->last_collisions = tracker.collisions;
  }
  c->out_frontier = next_frontier;
}

// ---------------------------------------------------------------- encoder
//
// Native v1 writer — full snapshots AND patches (mirror of
// encoding/encode.py encode_oplog; format spec: /root/reference/
// BINARY.md, reference writer src/list/encoding/encode_oplog.rs
// `encode` + `encode_from`). The txn walk below (StWalk) mirrors the
// Python SpanningTreeWalker's traversal ORDER exactly, so the native
// output is BYTE-identical to the Python writer's — pinned by
// tests/test_encode.py.

// Exact order mirror of listmerge/walker.py SpanningTreeWalker
// (reference: src/listmerge/txn_trace.rs:75-332), track_frontier=False
// shape: yields (consume) spans only. The Zone walker's cut refinement
// produces a different (equally causal) order; the encoders use THIS
// one because byte parity with the Python writer requires the same
// traversal.
struct StWalk {
  struct Ent {
    Span span;
    int np_global;
    std::vector<int32_t> par, child;
    bool visited = false;
  };
  std::vector<Ent> input;
  std::vector<int32_t> to_process;

  int find_ent(i64 t) const {
    int lo = 0, hi = (int)input.size();
    while (lo < hi) {
      int mid = (lo + hi) / 2;
      if (t < input[mid].span.start) hi = mid;
      else if (t >= input[mid].span.end) lo = mid + 1;
      else return mid;
    }
    return -1;
  }

  // rev_spans: descending span list (diff_rev output order)
  StWalk(const Graph& g, const std::vector<Span>& rev_spans) {
    std::vector<i64> ps;
    for (auto it = rev_spans.rbegin(); it != rev_spans.rend(); ++it) {
      i64 start = it->start, end = it->end;
      size_t i = g.find_idx(start);
      while (start < end) {
        i64 t_end = std::min(g.ends[i], end);
        Ent e;
        e.span = {start, t_end};
        g.parents_at(start, ps);
        e.np_global = (int)ps.size();
        for (i64 p : ps) {
          int pi = find_ent(p);
          if (pi >= 0) e.par.push_back((int32_t)pi);
        }
        if (e.par.empty()) to_process.push_back((int32_t)input.size());
        input.push_back(std::move(e));
        start = t_end;
        i++;
      }
    }
    for (size_t i = 0; i < input.size(); i++)
      for (int32_t p : input[i].par)
        input[(size_t)p].child.push_back((int32_t)i);
    std::reverse(to_process.begin(), to_process.end());
  }

  bool next(Span& consume) {
    if (to_process.empty()) return false;
    // prefer non-merge entries, most recently readied (walker.py
    // __next__ / txn_trace.rs:243-265)
    int32_t idx = to_process.back();
    if (input[(size_t)idx].np_global >= 2) {
      int found = -1;
      for (int ii = (int)to_process.size() - 1; ii >= 0; ii--)
        if (input[(size_t)to_process[ii]].np_global < 2) { found = ii;
          break; }
      if (found >= 0) {
        idx = to_process[(size_t)found];
        to_process[(size_t)found] = to_process.back();
        to_process.pop_back();
      } else {
        to_process.pop_back();
      }
    } else {
      to_process.pop_back();
    }
    Ent& e = input[(size_t)idx];
    e.visited = true;
    for (int32_t ci : e.child) {
      Ent& ce = input[(size_t)ci];
      if (ce.visited) continue;
      bool ready = true;
      for (int32_t p : ce.par)
        if (!input[(size_t)p].visited) { ready = false; break; }
      if (ready) to_process.push_back(ci);
    }
    consume = e.span;
    return true;
  }
};

extern "C" i64 dt_lz4_compress(const u8* src, i64 n, u8* out, i64 cap);
extern "C" i64 dt_crc32c(const u8* data, i64 n, i64 seed);

namespace enc {

static const u64 CH_FILEINFO = 1, CH_DOCID = 2, CH_AGENTNAMES = 3,
                 CH_USERDATA = 4, CH_COMPRESSED = 5, CH_STARTBRANCH = 10,
                 CH_VERSION = 12,
                 CH_CONTENT_COMPRESSED = 14, CH_PATCHES = 20,
                 CH_OP_VERSIONS = 21, CH_OP_TYPE_POS = 22,
                 CH_OP_PARENTS = 23, CH_PATCH_CONTENT = 24,
                 CH_CONTENT_KNOWN = 25, CH_CRC = 100;
static const u64 DATA_PLAIN_TEXT = 4;

struct Buf {
  std::vector<u8> b;
  void leb(u64 v) {
    do { u8 x = v & 0x7f; v >>= 7; b.push_back(v ? (u8)(x | 0x80) : x); }
    while (v);
  }
  void raw(const u8* p, size_t n) { b.insert(b.end(), p, p + n); }
  void chunk(u64 type, const std::vector<u8>& data) {
    leb(type); leb(data.size()); raw(data.data(), data.size());
  }
  void utf8(int32_t cp) {
    u32 c = (u32)cp;
    if (c < 0x80) b.push_back((u8)c);
    else if (c < 0x800) {
      b.push_back((u8)(0xC0 | (c >> 6)));
      b.push_back((u8)(0x80 | (c & 0x3F)));
    } else if (c < 0x10000) {
      b.push_back((u8)(0xE0 | (c >> 12)));
      b.push_back((u8)(0x80 | ((c >> 6) & 0x3F)));
      b.push_back((u8)(0x80 | (c & 0x3F)));
    } else {
      b.push_back((u8)(0xF0 | (c >> 18)));
      b.push_back((u8)(0x80 | ((c >> 12) & 0x3F)));
      b.push_back((u8)(0x80 | ((c >> 6) & 0x3F)));
      b.push_back((u8)(0x80 | (c & 0x3F)));
    }
  }
};

static inline u64 mix(u64 v, bool bit) { return (v << 1) | (bit ? 1 : 0); }
static inline u64 zz(i64 v) { return mix(v < 0 ? -v : v, v < 0); }

// One op run in the type/position column (encode.py _write_op).
static void write_op(Buf& out, u8 kind, i64 start, i64 end, bool fwd,
                     i64& cursor) {
  i64 length = end - start;
  fwd = fwd || length == 1;
  i64 op_start = (kind == DEL && !fwd) ? end : start;
  i64 op_end = (kind == INS && fwd) ? end : start;
  i64 diff = op_start - cursor;
  cursor = op_end;
  u64 n;
  if (length != 1) {
    n = (u64)length;
    if (kind == DEL) n = mix(n, fwd);
  } else if (diff != 0) {
    n = zz(diff);
  } else {
    n = 0;
  }
  n = mix(n, kind == DEL);
  n = mix(n, diff != 0);
  n = mix(n, length != 1);
  out.leb(n);
  if (length != 1 && diff != 0) out.leb(zz(diff));
}

}  // namespace enc

static i64 encode_impl(Ctx* c, const u8* docid, i64 docid_len,
                       const u8* userdata, i64 ud_len, bool store_ins,
                       bool compress, const std::vector<i64>& from_version) {
  using namespace enc;
  Graph& g = c->g;
  Agents& aa = c->aa;
  Ops& ops = c->ops;
  i64 top = 0;
  if (!ops.runs.empty()) {
    const OpRun& lr = ops.runs.back();
    top = lr.lv + (lr.end - lr.start);
  }

  // file-local agent numbering, 1-based, in order of first use
  std::vector<int> agent_map(aa.names.size(), 0);
  std::vector<i64> seq_cursor(aa.names.size(), 0);
  int next_agent = 1;
  Buf names_buf;
  auto map_agent = [&](i64 agent) -> int {
    int& m = agent_map[(size_t)agent];
    if (m == 0) {
      m = next_agent++;
      const std::string& nm = aa.names[(size_t)agent];
      names_buf.leb(nm.size());
      names_buf.raw((const u8*)nm.data(), nm.size());
    }
    return m;
  };

  Buf agent_chunk;
  // pending agent run: mapped, delta, n, agent, seq_end
  bool aa_pending = false;
  int pa_m = 0;
  i64 pa_delta = 0, pa_n = 0, pa_agent = 0, pa_seq_end = 0;
  auto flush_aa = [&]() {
    if (!aa_pending) return;
    agent_chunk.leb(mix((u64)pa_m, pa_delta != 0));
    agent_chunk.leb((u64)pa_n);
    if (pa_delta != 0) agent_chunk.leb(zz(pa_delta));
    aa_pending = false;
  };

  Buf ops_chunk;
  i64 ops_cursor = 0;
  bool op_pending = false;
  OpRun pend{};
  auto flush_op = [&]() {
    if (!op_pending) return;
    write_op(ops_chunk, pend.kind, pend.start, pend.end, pend.fwd,
             ops_cursor);
    op_pending = false;
  };

  // INS content column: utf8 chars + (len, known) RLE runs
  Buf ins_text;
  std::vector<std::pair<i64, bool>> ins_runs;
  bool ins_any = false;
  auto push_content = [&](const OpRun& piece) {
    ins_any = true;
    bool known = piece.cp >= 0;
    i64 n = piece.end - piece.start;
    if (known)
      for (i64 k = 0; k < n; k++)
        ins_text.utf8(c->ins_arena[(size_t)(piece.cp + k)]);
    if (!ins_runs.empty() && ins_runs.back().second == known)
      ins_runs.back().first += n;
    else
      ins_runs.emplace_back(n, known);
  };

  Buf txns_chunk;
  // local span start -> output start (ascending by local start)
  std::vector<i64> map_ls, map_os, map_n;
  i64 next_output = 0;
  auto map_local = [&](i64 p) -> i64 {
    size_t i = (size_t)(std::upper_bound(map_ls.begin(), map_ls.end(), p) -
                        map_ls.begin());
    if (i == 0) return -1;
    i--;
    if (p >= map_ls[i] + map_n[i]) return -1;
    return map_os[i] + (p - map_ls[i]);
  };
  std::vector<i64> ps;
  auto write_txn = [&](Span span) {
    i64 n = span.end - span.start;
    i64 out_start = next_output;
    size_t at = (size_t)(std::upper_bound(map_ls.begin(), map_ls.end(),
                                          span.start) - map_ls.begin());
    map_ls.insert(map_ls.begin() + at, span.start);
    map_os.insert(map_os.begin() + at, out_start);
    map_n.insert(map_n.begin() + at, n);
    next_output += n;
    txns_chunk.leb((u64)n);
    g.parents_at(span.start, ps);
    if (ps.empty()) { txns_chunk.leb(1); return; }  // foreign-ROOT marker
    for (size_t i = 0; i < ps.size(); i++) {
      bool has_more = i + 1 < ps.size();
      i64 mapped = map_local(ps[i]);
      if (mapped >= 0) {
        txns_chunk.leb(mix(mix((u64)(out_start - mapped), has_more), false));
      } else {
        i64 agent, seq;
        aa.local_to_agent(ps[i], agent, seq);
        txns_chunk.leb(mix(mix((u64)map_agent(agent), has_more), true));
        txns_chunk.leb((u64)seq);
      }
    }
  };

  // ---- main walk: spans above from_version, SpanningTreeWalker order
  std::vector<Span> walk_spans;
  if (from_version.empty()) {
    if (top > 0) walk_spans.push_back({0, top});
  } else {
    std::vector<Span> only_a;
    g.diff_rev(from_version, g.heads, only_a, walk_spans);
    if (!only_a.empty()) return -2;  // from_version not an ancestor
  }
  {
    StWalk w(g, walk_spans);
    Span consume;
    while (w.next(consume)) {
      if (span_empty(consume)) continue;
      // 1. agent assignment runs
      i64 pos = consume.start;
      while (pos < consume.end) {
        i64 agent, seq;
        aa.local_to_agent(pos, agent, seq);
        i64 n = aa.span_len(pos, consume.end - pos);
        int m = map_agent(agent);
        if (aa_pending && pa_m == m && pa_seq_end == seq) {
          pa_n += n;
          pa_seq_end = seq + n;
          seq_cursor[(size_t)pa_agent] = seq + n;
        } else {
          flush_aa();
          i64 delta = seq - seq_cursor[(size_t)agent];
          seq_cursor[(size_t)agent] = seq + n;
          aa_pending = true;
          pa_m = m; pa_delta = delta; pa_n = n; pa_agent = agent;
          pa_seq_end = seq + n;
        }
        pos += n;
      }
      // 2. ops + content
      size_t oi = ops.find_idx(consume.start);
      pos = consume.start;
      while (pos < consume.end) {
        const OpRun& run = ops.runs[oi];
        i64 run_end = run.lv + (run.end - run.start);
        i64 o1 = std::min(consume.end, run_end) - run.lv;
        OpRun piece = Ops::slice(run, pos - run.lv, o1);
        if (piece.kind == INS && store_ins) push_content(piece);
        i64 plen = piece.end - piece.start;
        i64 pdlen = pend.end - pend.start;
        bool appendable = false;
        if (op_pending && pend.kind == piece.kind) {
          // RLE append rule (op.py can_append_ops / op_metrics.rs:235)
          if ((pdlen == 1 || pend.fwd) && (plen == 1 || piece.fwd)) {
            if (piece.kind == INS && piece.start == pend.end)
              appendable = true;
            if (piece.kind == DEL && piece.start == pend.start)
              appendable = true;
          }
          if (!appendable && piece.kind == DEL &&
              (pdlen == 1 || !pend.fwd) && (plen == 1 || !piece.fwd) &&
              piece.end == pend.start)
            appendable = true;
        }
        if (appendable) {  // op.py append_ops
          bool fwd = piece.start >= pend.start &&
                     (piece.start != pend.start || piece.kind == DEL);
          pend.fwd = fwd;
          if (piece.kind == DEL && !fwd) pend.start = piece.start;
          else pend.end += plen;
        } else {
          flush_op();
          op_pending = true;
          pend = piece;
        }
        pos = run.lv + o1;
        oi++;
      }
      // 3. parents
      write_txn(consume);
    }
  }
  flush_aa();
  flush_op();

  // ---- assemble ----
  std::vector<u8> compress_blob;
  bool have_compressed_chunk = false;
  Buf patches;
  if (store_ins && ins_any) {
    Buf body;
    body.leb(0);  // kind = INS
    if (compress) {
      have_compressed_chunk = true;
      Buf inner;
      inner.leb(DATA_PLAIN_TEXT);
      inner.leb(ins_text.b.size());
      compress_blob.insert(compress_blob.end(), ins_text.b.begin(),
                           ins_text.b.end());
      body.chunk(CH_CONTENT_COMPRESSED, inner.b);
    } else {
      Buf inner;
      inner.leb(DATA_PLAIN_TEXT);
      inner.raw(ins_text.b.data(), ins_text.b.size());
      body.chunk(13 /* CH_CONTENT */, inner.b);
    }
    Buf runs;
    for (auto& r : ins_runs) runs.leb(mix((u64)r.first, r.second));
    body.chunk(CH_CONTENT_KNOWN, runs.b);
    patches.chunk(CH_PATCH_CONTENT, body.b);
  }

  // start branch BEFORE fileinfo: mapping the from version's agents may
  // append to names_buf, which fileinfo's CH_AGENTNAMES bakes below —
  // same build order as the Python writer (walk-first-use numbering,
  // then any from-only agents). Patch encodes carry no start-branch
  // content (ENCODE_PATCH).
  Buf start_branch;
  if (!from_version.empty()) {
    Buf vbuf;
    for (size_t i = 0; i < from_version.size(); i++) {
      bool has_more = i + 1 < from_version.size();
      i64 agent, seq;
      aa.local_to_agent(from_version[i], agent, seq);
      vbuf.leb(mix((u64)map_agent(agent), has_more));
      vbuf.leb((u64)seq);
    }
    start_branch.chunk(CH_VERSION, vbuf.b);
  }

  Buf fileinfo;
  if (docid_len >= 0) {
    Buf d;
    d.leb(DATA_PLAIN_TEXT);
    d.raw(docid, (size_t)docid_len);
    fileinfo.chunk(CH_DOCID, d.b);
  }
  fileinfo.chunk(CH_AGENTNAMES, names_buf.b);
  if (ud_len >= 0) {
    Buf d;
    d.raw(userdata, (size_t)ud_len);
    fileinfo.chunk(CH_USERDATA, d.b);
  }

  Buf result;
  const char magic[] = "DMNDTYPS";
  result.raw((const u8*)magic, 8);
  result.leb(0);  // PROTOCOL_VERSION
  if (have_compressed_chunk) {
    Buf comp;
    comp.leb(compress_blob.size());
    std::vector<u8> lz(compress_blob.size() + compress_blob.size() / 8 + 64);
    i64 ln = dt_lz4_compress(compress_blob.data(), (i64)compress_blob.size(),
                             lz.data(), (i64)lz.size());
    if (ln < 0) return -1;
    comp.raw(lz.data(), (size_t)ln);
    result.chunk(CH_COMPRESSED, comp.b);
  }
  result.chunk(CH_FILEINFO, fileinfo.b);
  result.chunk(CH_STARTBRANCH, start_branch.b);
  patches.chunk(CH_OP_VERSIONS, agent_chunk.b);
  patches.chunk(CH_OP_TYPE_POS, ops_chunk.b);
  patches.chunk(CH_OP_PARENTS, txns_chunk.b);
  result.chunk(CH_PATCHES, patches.b);

  u32 crc = (u32)dt_crc32c(result.b.data(), (i64)result.b.size(), 0);
  Buf crcb;
  crcb.b.assign({(u8)(crc & 0xFF), (u8)((crc >> 8) & 0xFF),
                 (u8)((crc >> 16) & 0xFF), (u8)((crc >> 24) & 0xFF)});
  result.chunk(CH_CRC, crcb.b);

  c->enc_buf = std::move(result.b);
  return (i64)c->enc_buf.size();
}

// ---------------------------------------------------------------- C ABI

extern "C" {

void* dt_ctx_new() { return new Ctx(); }
void dt_ctx_free(void* p) { delete (Ctx*)p; }

void dt_add_agent(void* p, const char* name) {
  Ctx* c = (Ctx*)p;
  c->aa.names.emplace_back(name);
  c->aa.client_runs.emplace_back();
}

// What a ctx derived from its columns at their old length: the tracker
// kept for the dumps, the compose cache (a new serial, so a packer that
// holds the old one marshals its columns instead) and the fetch buffers.
static void drop_derived(Ctx* c) {
  c->last_tracker.reset();
  c->zone_common.clear();
  c->composed.clear();
  c->compose_serial++;
  c->linear_pieces.clear();
  c->pack_steps.clear();
}

// Column loads. A `_tail` loader cuts its column back to `from` entries
// and appends `n`: the caller sends a column from the last entry it had
// sent onwards, since the log may have run-length-extended that entry in
// place, and from 0 for a whole load. Each returns the column's new
// length, or -1 (nothing changed) where the entries sent do not continue
// the ones kept: the caller then loads a new ctx whole. `pindptr` counts
// from the first entry sent.
i64 dt_load_graph_tail(void* p, i64 from, i64 n, const i64* starts,
                       const i64* ends, const i64* shadows,
                       const i64* pindptr, const i64* pflat) {
  Ctx* c = (Ctx*)p;
  Graph& g = c->g;
  size_t n_old = g.starts.size(), f = (size_t)from;
  if (from < 0 || n < 0) return -1;
  i64 keep = keep_below(f, n_old, f < n_old ? g.starts[f] : 0,
                        n_old ? g.ends.back() : 0, n, n ? starts[0] : 0,
                        n ? ends[0] : 0);
  if (keep < 0) return -1;
  drop_derived(c);
  g.starts.resize(f); g.starts.insert(g.starts.end(), starts, starts + n);
  g.ends.resize(f); g.ends.insert(g.ends.end(), ends, ends + n);
  g.shadows.resize(f); g.shadows.insert(g.shadows.end(), shadows, shadows + n);
  if (g.pindptr.empty()) g.pindptr.push_back(0);
  g.pindptr.resize(f + 1);
  i64 base = g.pindptr[f];
  g.pflat.resize((size_t)base);
  g.pflat.insert(g.pflat.end(), pflat, pflat + pindptr[n]);
  for (i64 i = 1; i <= n; i++) g.pindptr.push_back(base + pindptr[i]);
  g.reindex(f, keep);
  return (i64)g.starts.size();
}

i64 dt_load_agent_runs_tail(void* p, i64 from, i64 n, const i64* lv0,
                            const i64* lv1, const i64* agent,
                            const i64* seq0) {
  Ctx* c = (Ctx*)p;
  Agents& aa = c->aa;
  auto& gr = aa.global_runs;
  size_t n_old = gr.size(), f = (size_t)from;
  if (from < 0 || n < 0) return -1;
  for (i64 i = 0; i < n; i++)
    if (agent[i] < 0 || agent[i] >= (i64)aa.client_runs.size()) return -1;
  i64 keep = keep_below(f, n_old, f < n_old ? gr[f].lv0 : 0,
                        n_old ? gr.back().lv1 : 0, n, n ? lv0[0] : 0,
                        n ? lv1[0] : 0);
  if (keep < 0) return -1;
  drop_derived(c);
  // client_runs stay sorted by seq: the cut runs leave, the new ones are
  // inserted in their place
  auto by_seq = [](const AgentRun& a, const AgentRun& b) {
    return a.seq_start < b.seq_start;
  };
  for (size_t i = f; i < n_old; i++) {
    auto& runs = aa.client_runs[gr[i].agent];
    AgentRun r{gr[i].seq0, gr[i].seq0 + (gr[i].lv1 - gr[i].lv0), gr[i].lv0};
    auto it = std::lower_bound(runs.begin(), runs.end(), r, by_seq);
    while (it != runs.end() && it->lv_start != r.lv_start) ++it;
    if (it != runs.end()) runs.erase(it);
  }
  gr.resize(f);
  for (i64 i = 0; i < n; i++) {
    gr.push_back({lv0[i], lv1[i], agent[i], seq0[i]});
    auto& runs = aa.client_runs[agent[i]];
    AgentRun r{seq0[i], seq0[i] + (lv1[i] - lv0[i]), lv0[i]};
    runs.insert(std::upper_bound(runs.begin(), runs.end(), r, by_seq), r);
  }
  refill_idx(aa.idx_of, f, gr.size(), keep,
             [&](size_t i) { return Span{gr[i].lv0, gr[i].lv1}; });
  return (i64)gr.size();
}

i64 dt_load_ops_tail(void* p, i64 from, i64 n, const i64* lv, const u8* kind,
                     const u8* fwd, const i64* start, const i64* end,
                     const i64* cp) {
  Ctx* c = (Ctx*)p;
  auto& runs = c->ops.runs;
  size_t n_old = runs.size(), f = (size_t)from;
  if (from < 0 || n < 0) return -1;
  auto end_lv = [](const OpRun& r) { return r.lv + (r.end - r.start); };
  i64 keep = keep_below(f, n_old, f < n_old ? runs[f].lv : 0,
                        n_old ? end_lv(runs.back()) : 0, n, n ? lv[0] : 0,
                        n ? lv[0] + (end[0] - start[0]) : 0);
  if (keep < 0) return -1;
  drop_derived(c);
  runs.resize(f);
  runs.reserve(f + (size_t)n);
  for (i64 i = 0; i < n; i++)
    runs.push_back({lv[i], kind[i], fwd[i], start[i], end[i], cp[i]});
  refill_idx(c->ops.idx_of, f, runs.size(), keep,
             [&](size_t i) { return Span{runs[i].lv, end_lv(runs[i])}; });
  return (i64)runs.size();
}

i64 dt_load_ins_arena_tail(void* p, i64 from, i64 n, const int32_t* chars) {
  Ctx* c = (Ctx*)p;
  if (from < 0 || n < 0 || from > (i64)c->ins_arena.size()) return -1;
  c->ins_arena.resize((size_t)from);
  c->ins_arena.insert(c->ins_arena.end(), chars, chars + n);
  return (i64)c->ins_arena.size();
}

// whole loads into a new ctx (bench_main.cpp)
void dt_load_graph(void* p, i64 n, const i64* starts, const i64* ends,
                   const i64* shadows, const i64* pindptr, const i64* pflat) {
  dt_load_graph_tail(p, 0, n, starts, ends, shadows, pindptr, pflat);
}

void dt_load_agent_runs(void* p, i64 n, const i64* lv0, const i64* lv1,
                        const i64* agent, const i64* seq0) {
  dt_load_agent_runs_tail(p, 0, n, lv0, lv1, agent, seq0);
}

void dt_load_ops(void* p, i64 n, const i64* lv, const u8* kind,
                 const u8* fwd, const i64* start, const i64* end,
                 const i64* cp) {
  dt_load_ops_tail(p, 0, n, lv, kind, fwd, start, end, cp);
}

void dt_load_ins_arena(void* p, i64 n, const int32_t* chars) {
  dt_load_ins_arena_tail(p, 0, n, chars);
}

// transform: fills internal out buffer; returns count
i64 dt_transform(void* p, const i64* from, i64 nf, const i64* merge, i64 nm) {
  Ctx* c = (Ctx*)p;
  transform(c, std::vector<i64>(from, from + nf),
            std::vector<i64>(merge, merge + nm));
  return (i64)c->out.size();
}

// Full native merge: transform + materialize into the ctx's doc buffer.
// init (may be null/0) seeds the document. Returns final doc length.
i64 dt_merge_into_doc(void* p, const int32_t* init, i64 init_len,
                      const i64* from, i64 nf, const i64* merge, i64 nm) {
  Ctx* c = (Ctx*)p;
  c->doc = TextBuf();
  if (init_len > 0) c->doc.insert(0, init, init_len);
  c->merge_no_ff = (nf == 0 && init_len == 0);
  transform(c, std::vector<i64>(from, from + nf),
            std::vector<i64>(merge, merge + nm));
  c->merge_no_ff = false;
  PROF(doc);
  size_t rope_until = c->out.size();
  bool assemble = c->zone_ff_base && c->last_tracker != nullptr;
  if (assemble) rope_until = c->ff_split;
#ifdef DT_PROF
  i64 ff_lvs = 0;
  for (size_t oi = 0; oi < c->ff_split; oi++) ff_lvs += c->out[oi].len;
  fprintf(stderr,
          "merge_into_doc: assemble=%d rope_rows=%zu ff_split=%zu "
          "ff_lvs=%lld\n",
          (int)assemble, rope_until, (size_t)c->ff_split, (long long)ff_lvs);
#endif
  for (size_t oi = 0; oi < rope_until; oi++) {
    const XfOp& x = c->out[oi];
    if (x.pos < 0) continue;
    if (x.kind == INS) {
      // content chars for [lv, lv+len): arena offset via the op run's cp
      const OpRun& run = c->ops.runs[c->ops.find_idx(x.lv)];
      i64 cp = run.cp + (x.lv - run.lv);
      c->doc.insert(x.pos, c->ins_arena.data() + cp, x.len);
    } else {
      c->doc.erase(x.pos, x.len);
    }
  }
  if (assemble) {
    // Zone portion assembled STRAIGHT FROM THE TRACKER in one in-order
    // pass instead of per-op rope surgery: the content tree is already
    // in merged-document order, and an item is visible at the merged
    // version iff it was never deleted (everything in a forward merge's
    // zone is included in the merge frontier, so upstream-visibility
    // degenerates to !ever — same rule the device linearizer uses,
    // diamond_types_tpu/tpu/linearize.py). Underwater ids tile the rope
    // state after FF (zone_ff_base above); real ids pull arena content.
    std::vector<int32_t> base((size_t)c->doc.total);
    c->doc.dump(base.data());
    // two passes: exact-size the buffer, then raw copies (entries are
    // tiny on fragmented histories; per-entry vector bookkeeping costs
    // as much as the copy itself)
    i64 total = 0;
    for (BLeaf* lf = c->last_tracker->first_leaf; lf; lf = lf->next)
      for (int i = 0; i < lf->n; i++) {
        const BEntry& e = lf->e[i];
        if (e.ever) continue;
        if (e.ids >= UNDERWATER) {
          i64 p0 = e.ids - UNDERWATER;
          if (p0 >= (i64)base.size()) continue;   // placeholder tail
          total += std::min(e.len, (i64)base.size() - p0);
        } else {
          total += e.len;
        }
      }
    std::vector<int32_t> fin((size_t)total);
    int32_t* dst = fin.data();
    for (BLeaf* lf = c->last_tracker->first_leaf; lf; lf = lf->next)
      for (int i = 0; i < lf->n; i++) {
        const BEntry& e = lf->e[i];
        if (e.ever) continue;
        if (e.ids >= UNDERWATER) {
          i64 p0 = e.ids - UNDERWATER;
          if (p0 >= (i64)base.size()) continue;   // placeholder tail
          i64 n = std::min(e.len, (i64)base.size() - p0);
          std::memcpy(dst, base.data() + p0, (size_t)n * 4);
          dst += n;
        } else {
          const OpRun& run = c->ops.runs[c->ops.find_idx(e.ids)];
          i64 cp = run.cp + (e.ids - run.lv);
          std::memcpy(dst, c->ins_arena.data() + cp, (size_t)e.len * 4);
          dst += e.len;
        }
      }
    c->doc = TextBuf();
    if (!fin.empty()) c->doc.insert(0, fin.data(), (i64)fin.size());
  }
  // plain merges don't need the tracker afterwards — release its O(zone)
  // tables instead of pinning them on the context (dt_transform callers
  // that want dt_dump_tracker keep theirs); zone_common is cleared with it
  // so the dump/zone_common pair can never disagree about which transform
  // they describe
  c->last_tracker.reset();
  c->zone_common.clear();
  return c->doc.total;
}

void dt_get_doc(void* p, int32_t* out) { ((Ctx*)p)->doc.dump(out); }

void dt_get_out(void* p, i64* lv, i64* len, u8* kind, u8* fwd, i64* pos) {
  Ctx* c = (Ctx*)p;
  for (size_t i = 0; i < c->out.size(); i++) {
    lv[i] = c->out[i].lv;
    len[i] = c->out[i].len;
    kind[i] = c->out[i].kind;
    fwd[i] = c->out[i].fwd;
    pos[i] = c->out[i].pos;
  }
}

// Tracker item-table export (validation ground truth for the device
// linearizer, diamond_types_tpu/tpu/linearize.py): after dt_transform the
// last tracker is dumped in DOCUMENT ORDER as per-entry rows
// (ids, len, origin_left, origin_right, state, ever). Returns row count
// (call with null buffers to size). Rows include the underwater sentinel
// span(s); callers filter ids >= 1<<62.
i64 dt_dump_tracker(void* p, i64 cap, i64* ids, i64* len, i64* ol,
                    i64* orr, i64* state, u8* ever) {
  Ctx* c = (Ctx*)p;
  if (!c->last_tracker) return 0;
  i64 k = 0;
  for (BLeaf* lf = c->last_tracker->first_leaf; lf; lf = lf->next)
    for (int i = 0; i < lf->n; i++, k++)
      if (k < cap) {
        ids[k] = lf->e[i].ids;
        len[k] = lf->e[i].len;
        ol[k] = lf->e[i].ol;
        orr[k] = lf->e[i].orr;
        state[k] = lf->e[i].state;
        ever[k] = lf->e[i].ever ? 1 : 0;
      }
  return k;
}

// Delete-target table export: the last tracker's op-LV -> deleted-items
// map (lv0, lv1, t0, t1, fwd rows; op lv0+k targets item t0+k when fwd,
// t1-1-k when reversed). Recorded in apply order — callers sort by lv0.
// Same two-call sizing protocol as dt_dump_tracker. A delete op's target
// set is intrinsic to the op (fixed by its position + parent version),
// so these rows are valid for ANY schedule over the same conflict zone —
// the fork/join plan executor builds its write journal from them
// (diamond_types_tpu/tpu/plan_kernels.py).
i64 dt_dump_del_rows(void* p, i64 cap, i64* lv0, i64* lv1, i64* t0,
                     i64* t1, u8* fwd) {
  Ctx* c = (Ctx*)p;
  if (!c->last_tracker) return 0;
  const auto& dl = c->last_tracker->del_list;
  i64 k = 0;
  for (const DelRow& r : dl) {
    if (k < cap) {
      lv0[k] = r.lv0;
      lv1[k] = r.lv1;
      t0[k] = r.t0;
      t1[k] = r.t1;
      fwd[k] = r.fwd ? 1 : 0;
    }
    k++;
  }
  return k;
}

// Release the retained tracker + zone frontier (callers that are done
// with dt_dump_tracker / dt_get_zone_common free the O(zone) tables).
void dt_release_tracker(void* p) {
  Ctx* c = (Ctx*)p;
  c->last_tracker.reset();
  c->zone_common.clear();
}

// Common-ancestor frontier of the last transform's conflict zone.
i64 dt_get_zone_common(void* p, i64* buf, i64 cap) {
  Ctx* c = (Ctx*)p;
  i64 n = std::min((i64)c->zone_common.size(), cap);
  for (i64 i = 0; i < n; i++) buf[i] = c->zone_common[i];
  return (i64)c->zone_common.size();
}

i64 dt_get_out_frontier(void* p, i64* buf, i64 cap) {
  Ctx* c = (Ctx*)p;
  i64 n = std::min((i64)c->out_frontier.size(), cap);
  for (i64 i = 0; i < n; i++) buf[i] = c->out_frontier[i];
  return (i64)c->out_frontier.size();
}

// Structured merge-kernel event counters (process-global; order matches
// native/core.py EVENT_COUNTER_NAMES). Returns the counter count.
i64 dt_get_counters(unsigned long long* out, i64 cap) {
  const unsigned long long vals[] = {
      g_events.integrate_calls, g_events.integrate_scan_iters,
      g_events.apply_ins_runs, g_events.apply_del_runs,
      g_events.advance_calls, g_events.retreat_calls,
      g_events.walk_steps, g_events.diff_calls};
  i64 k = (i64)(sizeof(vals) / sizeof(vals[0]));
  for (i64 i = 0; i < std::min(cap, k); i++) out[i] = vals[i];
  return k;
}

void dt_reset_counters() { g_events = EventCounters{}; }

// Colliding concurrent inserts during the last dt_transform on this ctx
// (reference: has_conflicts_when_merging, src/list/merge.rs:51).
i64 dt_last_collisions(void* p) { return ((Ctx*)p)->last_collisions; }

// ---- zone-engine composer (host prep; see Composer above) ----
//
// Protocol: dt_compose_plan composes every entry span and caches the
// results in the ctx; dt_compose_counts reports per-entry sizes (5 i64
// each: nq, nch, nblk, ndel_base, ndel_own); dt_compose_fetch fills the
// caller's flat arrays (entry-concatenated, entry-local indices) and
// frees the cache. Returns -1 on unsupported input (reverse insert
// runs / out-of-range positions) — caller falls back to Python.
i64 dt_compose_plan(void* p, i64 n, const i64* s0, const i64* s1) {
  Ctx* c = (Ctx*)p;
  c->compose_serial++;
  c->composed.clear();
  c->composed.resize((size_t)n);
  for (i64 k = 0; k < n; k++) {
    Composer comp(true);
    if (!compose_span_ops(c, comp, {s0[k], s1[k]})) {
      c->composed.clear();
      return -1;
    }
    comp.finish(c->composed[k]);
  }
  return 0;
}

i64 dt_compose_serial(void* p) { return ((Ctx*)p)->compose_serial; }

void dt_compose_counts(void* p, i64* out) {
  Ctx* c = (Ctx*)p;
  for (size_t k = 0; k < c->composed.size(); k++) {
    const ComposedOut& o = c->composed[k];
    out[k * 5 + 0] = (i64)o.q_cursor.size();
    out[k * 5 + 1] = (i64)o.ch_lv.size();
    out[k * 5 + 2] = (i64)o.blk_start.size();
    out[k * 5 + 3] = (i64)o.db0.size();
    out[k * 5 + 4] = (i64)o.do0.size();
  }
}

void dt_compose_fetch(void* p, i64* q, i64* ch_lv, int32_t* ch_block,
                      u8* ch_head, u8* ch_kind, i64* ch_anchor,
                      int32_t* ch_q, i64* ch_headlv, i64* ch_orrown,
                      int32_t* blk_root_q, i64* blk_root_lv,
                      int32_t* blk_start, int32_t* blk_len,
                      i64* db0, i64* db1, i64* do0, i64* do1) {
  Ctx* c = (Ctx*)p;
  size_t iq = 0, ic = 0, ib = 0, idb = 0, ido = 0;
  for (const ComposedOut& o : c->composed) {
    std::copy(o.q_cursor.begin(), o.q_cursor.end(), q + iq);
    iq += o.q_cursor.size();
    std::copy(o.ch_lv.begin(), o.ch_lv.end(), ch_lv + ic);
    std::copy(o.ch_block.begin(), o.ch_block.end(), ch_block + ic);
    std::copy(o.ch_head.begin(), o.ch_head.end(), ch_head + ic);
    std::copy(o.ch_kind.begin(), o.ch_kind.end(), ch_kind + ic);
    std::copy(o.ch_anchor.begin(), o.ch_anchor.end(), ch_anchor + ic);
    std::copy(o.ch_q.begin(), o.ch_q.end(), ch_q + ic);
    std::copy(o.ch_headlv.begin(), o.ch_headlv.end(), ch_headlv + ic);
    std::copy(o.ch_orrown.begin(), o.ch_orrown.end(), ch_orrown + ic);
    ic += o.ch_lv.size();
    std::copy(o.blk_root_q.begin(), o.blk_root_q.end(), blk_root_q + ib);
    std::copy(o.blk_root_lv.begin(), o.blk_root_lv.end(), blk_root_lv + ib);
    std::copy(o.blk_start.begin(), o.blk_start.end(), blk_start + ib);
    std::copy(o.blk_len.begin(), o.blk_len.end(), blk_len + ib);
    ib += o.blk_start.size();
    std::copy(o.db0.begin(), o.db0.end(), db0 + idb);
    std::copy(o.db1.begin(), o.db1.end(), db1 + idb);
    idb += o.db0.size();
    std::copy(o.do0.begin(), o.do0.end(), do0 + ido);
    std::copy(o.do1.begin(), o.do1.end(), do1 + ido);
    ido += o.do0.size();
  }
  c->composed.clear();
  c->composed.shrink_to_fit();
}

// ---------------------------------------------------------------- zone pack
//
// Native zone tape packer (VERDICT r4 #6 — the ~280 ms pure-Python
// pack was the zone engine's remaining host-prep cost): flattens a
// prepared zone (plan actions + composed entries) into the micro-step
// tape arrays of diamond_types_tpu/tpu/zone_kernel.py::pack_zone_tape,
// ARRAY-IDENTICAL to the Python packer (pinned by
// tests/test_zone_kernel.py). Composed entries arrive as the
// entry-concatenated flat columns (counts-prefixed, same layout as
// dt_compose_fetch) so the packer serves both the native and the
// Python fallback composer.


// action columns: kind (plan2 BEGIN=0 FORK=1 MAX=2 DROP=3 APPLY=4),
// a, b per plan.actions semantics. Composed flat columns per the
// counts[5*n] layout. slot map: ins_lv0/ins_cum sorted run table.
// Returns total step count; the caller fetches with dt_zone_pack_fetch
// on the same ctx (the step buffer lives on the ctx — single-threaded
// per ctx, like every other two-call protocol in this file).
// use_cache: read composed entries straight from the ctx's compose
// cache (populated by the immediately-preceding dt_compose_plan) and
// ignore the flat column pointers (they may be null).
extern "C" i64 dt_zone_pack(
    void* p, i64 n_actions, const i64* act_kind, const i64* act_a, const i64* act_b,
    i64 n_entries, const i64* counts, const i64* flat_q, const i64* ch_lv,
    const u8* ch_kind, const i64* ch_anchor, const int32_t* ch_q,
    const i64* ch_orrown, const int32_t* blk_root_q, const i64* blk_root_lv,
    const int32_t* blk_start, const int32_t* blk_len, const i64* db0,
    const i64* db1, const i64* do0, const i64* do1, i64 n_runs,
    const i64* ins_lv0, const i64* ins_cum, i64 plen, const i64* agent_k,
    const i64* seq_k, i64 MB, i64 MC, i64 MD, i64 use_cache) {
  // use_cache > 0 is the expected compose serial: both the entry count
  // AND the cache identity must match (two plans can have equal counts)
  Ctx* cx = (Ctx*)p;
  if (use_cache && ((i64)cx->composed.size() != n_entries ||
                    cx->compose_serial != use_cache))
    return -2;  // stale/absent cache: caller re-marshals
  using zonepack::Step;
  const int K_OWN = 1;
  const int OP_BEGIN = 0, OP_FORK = 1, OP_MAX = 2, OP_APPLY = 3;
  const int A_BEGIN = 0, A_FORK = 1, A_MAX = 2, A_DROP = 3, A_APPLY = 4;

  auto slot_of = [&](i64 lv) -> i64 {
    // searchsorted(ins_lv0, lv, 'right') - 1
    const i64* hi = std::upper_bound(ins_lv0, ins_lv0 + n_runs, lv);
    i64 j = (hi - ins_lv0) - 1;
    return plen + ins_cum[j] + (lv - ins_lv0[j]);
  };

  // per-entry offsets into the flat columns (marshalled path only)
  std::vector<i64> off_q, off_ch, off_blk, off_db, off_do;
  if (!use_cache) {
    off_q.assign(n_entries + 1, 0); off_ch.assign(n_entries + 1, 0);
    off_blk.assign(n_entries + 1, 0); off_db.assign(n_entries + 1, 0);
    off_do.assign(n_entries + 1, 0);
    for (i64 k = 0; k < n_entries; k++) {
      off_q[k + 1] = off_q[k] + counts[k * 5 + 0];
      off_ch[k + 1] = off_ch[k] + counts[k * 5 + 1];
      off_blk[k + 1] = off_blk[k] + counts[k * 5 + 2];
      off_db[k + 1] = off_db[k] + counts[k * 5 + 3];
      off_do[k + 1] = off_do[k] + counts[k * 5 + 4];
    }
  }

  // uniform per-entry view over either source
  struct EView {
    const i64* q; i64 nq;
    const i64 *lv, *anchor, *orrown; const u8* kind;
    const int32_t* qidx; i64 nc;
    const int32_t *brq, *bstart, *blen; const i64* brlv; i64 nb;
    const i64 *pdb0, *pdb1; i64 ndb;
    const i64 *pdo0, *pdo1; i64 ndo;
  };
  auto view_of = [&](i64 e) -> EView {
    EView v;
    if (use_cache) {
      const ComposedOut& o = cx->composed[(size_t)e];
      v.q = o.q_cursor.data(); v.nq = (i64)o.q_cursor.size();
      v.lv = o.ch_lv.data(); v.anchor = o.ch_anchor.data();
      v.orrown = o.ch_orrown.data(); v.kind = o.ch_kind.data();
      v.qidx = o.ch_q.data(); v.nc = (i64)o.ch_lv.size();
      v.brq = o.blk_root_q.data(); v.bstart = o.blk_start.data();
      v.blen = o.blk_len.data(); v.brlv = o.blk_root_lv.data();
      v.nb = (i64)o.blk_start.size();
      v.pdb0 = o.db0.data(); v.pdb1 = o.db1.data();
      v.ndb = (i64)o.db0.size();
      v.pdo0 = o.do0.data(); v.pdo1 = o.do1.data();
      v.ndo = (i64)o.do0.size();
    } else {
      v.q = flat_q + off_q[e]; v.nq = counts[e * 5 + 0];
      v.lv = ch_lv + off_ch[e]; v.anchor = ch_anchor + off_ch[e];
      v.orrown = ch_orrown + off_ch[e]; v.kind = ch_kind + off_ch[e];
      v.qidx = ch_q + off_ch[e]; v.nc = counts[e * 5 + 1];
      v.brq = blk_root_q + off_blk[e]; v.bstart = blk_start + off_blk[e];
      v.blen = blk_len + off_blk[e]; v.brlv = blk_root_lv + off_blk[e];
      v.nb = counts[e * 5 + 2];
      v.pdb0 = db0 + off_db[e]; v.pdb1 = db1 + off_db[e];
      v.ndb = counts[e * 5 + 3];
      v.pdo0 = do0 + off_do[e]; v.pdo1 = do1 + off_do[e];
      v.ndo = counts[e * 5 + 4];
    }
    return v;
  };

  zonepack::PackState ps;
  ps.MB = MB; ps.MC = MC; ps.MD = MD;
  ps.steps.reserve((size_t)n_actions * 2);

  for (i64 ai = 0; ai < n_actions; ai++) {
    i64 kind = act_kind[ai];
    if (kind == A_BEGIN) {
      ps.new_step(OP_BEGIN, (int32_t)act_a[ai], 0, 0);
    } else if (kind == A_FORK) {
      ps.new_step(OP_FORK, (int32_t)act_a[ai], (int32_t)act_b[ai], 0);
    } else if (kind == A_MAX) {
      // tape a = src, b = dst (zone_kernel.py:257)
      ps.new_step(OP_MAX, (int32_t)act_b[ai], (int32_t)act_a[ai], 0);
    } else if (kind == A_DROP) {
      continue;
    } else if (kind == A_APPLY) {
      i64 e = act_a[ai];
      int32_t row = (int32_t)act_b[ai];
      Step* cur = ps.new_step(OP_APPLY, row, 0, 1);
      auto next_sub = [&]() { return ps.new_step(OP_APPLY, row, 0, 0); };

      const EView v = view_of(e);
      auto q_at = [&](i64 qi) -> i64 {
        // Python: flat_q[clip(ch_q, 0, None)] with a zeros(1) fallback
        // when the entry has no queries
        if (v.nq == 0) return 0;
        return v.q[qi >= 0 ? qi : 0];
      };
      auto char_cols = [&](i64 pos, int32_t* out7, int32_t blk) {
        i64 slot = slot_of(v.lv[pos]);
        int kd = v.kind[pos];
        i64 anchor = v.anchor[pos] >= 0 ? slot_of(v.anchor[pos]) : -1;
        i64 orr = v.orrown[pos] >= 0 ? slot_of(v.orrown[pos]) : -1;
        i64 c_of = q_at(v.qidx[pos]);
        i64 ol_static, ol_coord;
        if (kd == 0) ol_static = slot - 1;
        else if (kd == K_OWN) ol_static = anchor;
        else ol_static = (c_of == 0) ? -1 : -2;
        ol_coord = (kd >= 2 && c_of > 0) ? c_of : 0;
        out7[0] = (int32_t)slot;
        out7[1] = (int32_t)ol_static;
        out7[2] = (int32_t)ol_coord;
        out7[3] = (int32_t)orr;
        out7[4] = blk;
        out7[5] = (int32_t)agent_k[slot];
        out7[6] = (int32_t)seq_k[slot];
      };

      if (v.nc) {
        for (i64 b = 0; b < v.nb; b++) {
          i64 lo = v.bstart[b];
          i64 hi = lo + v.blen[b];
          bool first = true;
          i64 pos = lo;
          while (pos < hi) {
            if ((i64)cur->blocks.size() >= MB ||
                (i64)cur->chars.size() >= MC)
              cur = next_sub();
            i64 take = std::min(hi - pos, MC - (i64)cur->chars.size());
            int32_t cursor = first ? (int32_t)v.q[v.brq[b]] : -2;
            int32_t prev = first ? -1 : (int32_t)slot_of(v.lv[pos - 1]);
            cur->blocks.push_back(std::array<int32_t, 5>{{
                cursor, prev, (int32_t)slot_of(v.brlv[b]),
                (int32_t)cur->chars.size(), (int32_t)take}});
            int32_t blk = (int32_t)cur->blocks.size() - 1;
            for (i64 k = 0; k < take; k++) {
              std::array<int32_t, 7> row7;
              char_cols(pos + k, row7.data(), blk);
              cur->chars.push_back(row7);
            }
            pos += take;
            first = false;
          }
        }
      }
      for (i64 d = 0; d < v.ndb; d++) {
        if ((i64)cur->dels.size() >= MD) cur = next_sub();
        cur->dels.push_back(std::array<int32_t, 3>{{
            0, (int32_t)v.pdb0[d], (int32_t)v.pdb1[d]}});
      }
      for (i64 d = 0; d < v.ndo; d++) {
        if ((i64)cur->dels.size() >= MD) cur = next_sub();
        i64 s0 = slot_of(v.pdo0[d]);
        cur->dels.push_back(std::array<int32_t, 3>{{
            1, (int32_t)s0, (int32_t)(s0 + (v.pdo1[d] - v.pdo0[d]))}});
      }
    } else {
      return -1;  // unknown action kind
    }
  }
  if (use_cache) {
    // consumed: a long-lived ctx must not pin O(document) composed
    // columns after the pack (the fetch path clears its own copy)
    cx->composed.clear();
    cx->composed.shrink_to_fit();
  }
  cx->pack_steps = std::move(ps.steps);
  return (i64)cx->pack_steps.size();
}

// Fill the caller's [T]-and-[T,M]-shaped arrays INCLUDING the pad
// cells (the caller allocates with np.empty — zero/pad-initializing
// ~100 MB of tape in numpy costs more than writing it once here) and
// free the buffer. Pads: blk_cursor/blk_prev/ch_slot/ch_ol_static/
// del_kind -1, everything else 0.
extern "C" void dt_zone_pack_fetch(
    void* p, int32_t* op, int32_t* arg_a, int32_t* arg_b, int32_t* snap_flag,
    int32_t* blk_cursor, int32_t* blk_prev, int32_t* blk_root,
    int32_t* blk_start_o, int32_t* blk_len_o, int32_t* ch_slot,
    int32_t* ch_ol_static, int32_t* ch_ol_coord, int32_t* ch_orr_own,
    int32_t* ch_blk, int32_t* ch_agent, int32_t* ch_seq, int32_t* del_kind,
    int32_t* del_a, int32_t* del_b, i64 MB, i64 MC, i64 MD) {
  Ctx* c = (Ctx*)p;
  i64 T = (i64)c->pack_steps.size();
  i64 Tp = T > 0 ? T : 1;
  std::memset(op, 0, (size_t)Tp * 4);
  std::memset(arg_a, 0, (size_t)Tp * 4);
  std::memset(arg_b, 0, (size_t)Tp * 4);
  std::memset(snap_flag, 0, (size_t)Tp * 4);
  std::memset(blk_cursor, 0xFF, (size_t)(Tp * MB) * 4);   // -1
  std::memset(blk_prev, 0xFF, (size_t)(Tp * MB) * 4);     // -1
  std::memset(blk_root, 0, (size_t)(Tp * MB) * 4);
  std::memset(blk_start_o, 0, (size_t)(Tp * MB) * 4);
  std::memset(blk_len_o, 0, (size_t)(Tp * MB) * 4);
  std::memset(ch_slot, 0xFF, (size_t)(Tp * MC) * 4);      // -1
  std::memset(ch_ol_static, 0xFF, (size_t)(Tp * MC) * 4); // -1
  std::memset(ch_ol_coord, 0, (size_t)(Tp * MC) * 4);
  std::memset(ch_orr_own, 0xFF, (size_t)(Tp * MC) * 4);   // -1
  std::memset(ch_blk, 0, (size_t)(Tp * MC) * 4);
  std::memset(ch_agent, 0, (size_t)(Tp * MC) * 4);
  std::memset(ch_seq, 0, (size_t)(Tp * MC) * 4);
  std::memset(del_kind, 0xFF, (size_t)(Tp * MD) * 4);     // -1
  std::memset(del_a, 0, (size_t)(Tp * MD) * 4);
  std::memset(del_b, 0, (size_t)(Tp * MD) * 4);
  for (size_t t = 0; t < c->pack_steps.size(); t++) {
    const zonepack::Step& s = c->pack_steps[t];
    op[t] = s.op; arg_a[t] = s.a; arg_b[t] = s.b; snap_flag[t] = s.snap;
    for (size_t i = 0; i < s.blocks.size(); i++) {
      blk_cursor[t * MB + i] = s.blocks[i][0];
      blk_prev[t * MB + i] = s.blocks[i][1];
      blk_root[t * MB + i] = s.blocks[i][2];
      blk_start_o[t * MB + i] = s.blocks[i][3];
      blk_len_o[t * MB + i] = s.blocks[i][4];
    }
    for (size_t i = 0; i < s.chars.size(); i++) {
      ch_slot[t * MC + i] = s.chars[i][0];
      ch_ol_static[t * MC + i] = s.chars[i][1];
      ch_ol_coord[t * MC + i] = s.chars[i][2];
      ch_orr_own[t * MC + i] = s.chars[i][3];
      ch_blk[t * MC + i] = s.chars[i][4];
      ch_agent[t * MC + i] = s.chars[i][5];
      ch_seq[t * MC + i] = s.chars[i][6];
    }
    for (size_t i = 0; i < s.dels.size(); i++) {
      del_kind[t * MD + i] = s.dels[i][0];
      del_a[t * MD + i] = s.dels[i][1];
      del_b[t * MD + i] = s.dels[i][2];
    }
  }
  c->pack_steps.clear();
  c->pack_steps.shrink_to_fit();
}

// Graph rebuild from decoded rows (decode.py _rebuild_from_native's hot
// loop): RLE-merge linear rows, compute shadows, sort parents, and emit
// the version frontier — the exact incremental semantics of
// causalgraph/graph.py::push + _advance_known_run, batch-applied.
// Outputs (caller-allocated at n / len(par) upper bounds): merged
// starts/ends/shadows, parent CSR (pindptr[m+1], pflat), child CSR
// (cindptr[m+1], cflat, croot with its count in croot_n[0]), version
// (ascending; count in ver_n[0]). Returns the merged run count m.
extern "C" i64 dt_graph_rebuild(i64 n, const i64* start, const i64* end,
                                const i64* off, const i64* par,
                                i64* m_starts, i64* m_ends, i64* m_shadows,
                                i64* m_pindptr, i64* m_pflat,
                                i64* m_cindptr, i64* m_cflat, i64* m_croot,
                                i64* croot_n, i64* ver_out, i64* ver_n) {
  i64 m = 0;
  i64 pk = 0;
  m_pindptr[0] = 0;
  std::vector<i64> psort;
  auto find_idx = [&](i64 v) -> i64 {
    // binary search over the merged runs built so far
    i64 lo = 0, hi = m;
    while (lo < hi) {
      i64 mid = (lo + hi) / 2;
      if (v < m_starts[mid]) hi = mid;
      else if (v >= m_ends[mid]) lo = mid + 1;
      else return mid;
    }
    return -1;
  };
  for (i64 i = 0; i < n; i++) {
    i64 np = off[i + 1] - off[i];
    const i64* ps = par + off[i];
    // parents must reference EARLIER LVs: the per-row Python path
    // rejects forward references loudly (find_idx KeyError), and a
    // batch path that resolved them after the fact would install a
    // silently-corrupt graph
    for (i64 k = 0; k < np; k++)
      if (ps[k] >= start[i]) return -1;
    // RLE extend: linear continuation of the previous run
    if (m > 0 && np == 1 && ps[0] == m_ends[m - 1] - 1 &&
        m_ends[m - 1] == start[i]) {
      m_ends[m - 1] = end[i];
      continue;
    }
    // shadow walk (graph.py push)
    i64 shadow = start[i];
    bool moved = true;
    while (moved && shadow >= 1) {
      moved = false;
      for (i64 k = 0; k < np; k++) {
        if (ps[k] == shadow - 1) {
          i64 j = find_idx(shadow - 1);
          if (j < 0) return -1;  // corrupt rows: caller falls back
          shadow = m_shadows[j];
          moved = true;
          break;
        }
      }
    }
    m_starts[m] = start[i];
    m_ends[m] = end[i];
    m_shadows[m] = shadow;
    psort.assign(ps, ps + np);
    std::sort(psort.begin(), psort.end());
    for (i64 v : psort) m_pflat[pk++] = v;
    m_pindptr[m + 1] = pk;
    m++;
  }
  // child CSR + roots
  std::fill(m_cindptr, m_cindptr + m + 1, 0);
  i64 nroot = 0;
  for (i64 i = 0; i < m; i++) {
    i64 np = m_pindptr[i + 1] - m_pindptr[i];
    if (np == 0) m_croot[nroot++] = i;
    for (i64 k = m_pindptr[i]; k < m_pindptr[i + 1]; k++) {
      i64 j = find_idx(m_pflat[k]);
      if (j < 0) return -1;  // corrupt rows: caller falls back
      m_cindptr[j + 1]++;
    }
  }
  croot_n[0] = nroot;
  for (i64 i = 0; i < m; i++) m_cindptr[i + 1] += m_cindptr[i];
  {
    std::vector<i64> fill(m_cindptr, m_cindptr + m);
    for (i64 i = 0; i < m; i++)
      for (i64 k = m_pindptr[i]; k < m_pindptr[i + 1]; k++)
        m_cflat[fill[(size_t)find_idx(m_pflat[k])]++] = i;
  }
  // version frontier: entry-final LVs never referenced as a parent
  {
    std::vector<i64> allp(m_pflat, m_pflat + pk);
    std::sort(allp.begin(), allp.end());
    i64 kv = 0;
    for (i64 i = 0; i < m; i++) {
      i64 last = m_ends[i] - 1;
      if (!std::binary_search(allp.begin(), allp.end(), last))
        ver_out[kv++] = last;
    }
    ver_n[0] = kv;
  }
  return m;
}

// Zone insert-run collection (prepare_zone's table pass — ~50k
// Python piece iterations on node_nodecc): INS sub-runs of the given
// (disjoint, ascending) spans as (lv0, len, arena cp) columns. Returns
// the run count, or -1 when an insert lacks stored content. The caller
// sizes the outputs at #op_runs + #spans (a span boundary can split a
// run, adding at most one piece per span edge).
extern "C" i64 dt_zone_ins_runs(void* p, i64 nspans, const i64* s0,
                                const i64* s1, i64* lv0, i64* len_out,
                                i64* cp_out) {
  Ctx* c = (Ctx*)p;
  i64 k = 0;
  for (i64 i = 0; i < nspans; i++) {
    i64 lo = s0[i], hi = s1[i];
    if (hi <= lo) continue;
    size_t oi = c->ops.find_idx(lo);
    i64 pos = lo;
    while (pos < hi) {
      const OpRun& run = c->ops.runs[oi];
      i64 run_end = run.lv + (run.end - run.start);
      i64 o0 = pos - run.lv;
      i64 o1 = std::min(hi, run_end) - run.lv;
      if (run.kind == INS) {
        if (run.cp < 0) return -1;  // zone insert without stored content
        lv0[k] = run.lv + o0;
        len_out[k] = o1 - o0;
        cp_out[k] = run.cp + o0;
        k++;
      }
      pos = run.lv + o1;
      oi++;
    }
  }
  return k;
}

// Linear fast-forward prefix composition (assemble_prefix's hot loop):
// compose the (sorted, causally linear) spans over an EMPTY base and
// return the alive own pieces in document order — the caller joins their
// arena content. Returns piece count, or -1 on unsupported input.
i64 dt_compose_linear(void* p, i64 nspans, const i64* s0, const i64* s1) {
  Ctx* c = (Ctx*)p;
  Composer comp(false);
  for (i64 k = 0; k < nspans; k++)
    if (!compose_span_ops(c, comp, {s0[k], s1[k]})) return -1;
  c->linear_pieces.clear();
  std::vector<int> st;
  int cur = comp.root;
  while (!st.empty() || cur >= 0) {
    while (cur >= 0) { st.push_back(cur); cur = comp.A[cur].l; }
    cur = st.back();
    st.pop_back();
    const CompPiece& pc = comp.A[cur];
    if (pc.base < 0 && pc.alive)
      c->linear_pieces.emplace_back(pc.lv, pc.length);
    cur = pc.r;
  }
  return (i64)c->linear_pieces.size();
}

void dt_fetch_linear(void* p, i64* lv, i64* len) {
  Ctx* c = (Ctx*)p;
  for (size_t i = 0; i < c->linear_pieces.size(); i++) {
    lv[i] = c->linear_pieces[i].first;
    len[i] = c->linear_pieces[i].second;
  }
  c->linear_pieces.clear();
  c->linear_pieces.shrink_to_fit();
}

// Native full-snapshot v1 encode (see encode_full_impl above). docid_len /
// ud_len of -1 mean "absent". Returns the encoded size (fetch with
// dt_encode_fetch) or -1 on failure (caller falls back to Python).
i64 dt_encode_full(void* p, const u8* docid, i64 docid_len,
                   const u8* userdata, i64 ud_len, i64 store_ins,
                   i64 compress) {
  return encode_impl((Ctx*)p, docid, docid_len, userdata, ud_len,
                     store_ins != 0, compress != 0, {});
}

// Patch encode (reference: encode_oplog.rs encode_from): ops above
// `from` only, start branch = `from` as agent versions, no start-branch
// content. Returns -2 when `from` is not an ancestor of the oplog tip.
i64 dt_encode_patch(void* p, const u8* docid, i64 docid_len,
                    const u8* userdata, i64 ud_len, i64 store_ins,
                    i64 compress, const i64* from, i64 nf) {
  return encode_impl((Ctx*)p, docid, docid_len, userdata, ud_len,
                     store_ins != 0, compress != 0,
                     std::vector<i64>(from, from + nf));
}

void dt_encode_fetch(void* p, u8* out) {
  Ctx* c = (Ctx*)p;
  std::memcpy(out, c->enc_buf.data(), c->enc_buf.size());
  c->enc_buf.clear();
  c->enc_buf.shrink_to_fit();
}

}  // extern "C"
