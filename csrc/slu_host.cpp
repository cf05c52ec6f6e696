// Native host-side graph algorithms for the TPU sparse direct solver.
//
// C++ implementations of the sequential preprocessing passes that the
// reference implements in C (per-function citations below), exposed
// through a minimal C ABI consumed via ctypes
// (superlu_dist_tpu/utils/native.py).  The Python versions in
// superlu_dist_tpu/plan/ remain the portable fallback and the test
// oracle (tests/test_native.py compares the two).
//
//   slu_etree      — elimination tree        (reference SRC/etree.c)
//   slu_postorder  — forest postorder        (reference SRC/etree.c)
//   slu_colcounts  — Cholesky column counts  (reference SRC/symbfact.c:81
//                    derives the same quantity while factorizing)
//   slu_mdorder    — minimum-degree ordering (reference SRC/mmd.c genmmd)
//   slu_mc64       — static-pivoting row permutation, max product of
//                    diagonal magnitudes with dual-variable scalings
//                    (reference SRC/mc64ad_dist.c:121, job=5)
//   slu_symbfact_* — supernodal symbolic factorization on the
//                    symmetrized pattern (reference SRC/symbfact.c:81)
//
// All index arrays are int64 (the reference's _LONGINT / XSDK_INDEX_SIZE
// 64 mode, SRC/superlu_defs.h).

#include <algorithm>
#include <atomic>
#include <cmath>
#include <cstdint>
#include <cstring>
#include <limits>
#include <numeric>
#include <queue>
#include <thread>
#include <vector>

#include "slu_cpuid.h"

using std::int64_t;

extern "C" {

// ---------------------------------------------------------------- etree
// Liu's algorithm with path compression on the symmetric pattern
// (indptr/indices CSR; only i<j pairs are used).
void slu_etree(int64_t n, const int64_t* indptr, const int64_t* indices,
               int64_t* parent) {
  std::vector<int64_t> ancestor(n, -1);
  for (int64_t j = 0; j < n; ++j) parent[j] = -1;
  for (int64_t j = 0; j < n; ++j) {
    for (int64_t p = indptr[j]; p < indptr[j + 1]; ++p) {
      int64_t i = indices[p];
      if (i >= j) continue;
      int64_t r = i;
      while (true) {
        int64_t a = ancestor[r];
        if (a == j) break;
        ancestor[r] = j;
        if (a == -1) { parent[r] = j; break; }
        r = a;
      }
    }
  }
}

// ------------------------------------------------------------ postorder
// Iterative DFS over the forest, children visited in ascending order.
void slu_postorder(int64_t n, const int64_t* parent, int64_t* post) {
  std::vector<int64_t> head(n, -1), nxt(n, -1), stack;
  for (int64_t j = n - 1; j >= 0; --j) {
    int64_t p = parent[j];
    if (p != -1) { nxt[j] = head[p]; head[p] = j; }
  }
  int64_t k = 0;
  stack.reserve(64);
  for (int64_t root = 0; root < n; ++root) {
    if (parent[root] != -1) continue;
    stack.push_back(root);
    while (!stack.empty()) {
      int64_t node = stack.back();
      int64_t child = head[node];
      if (child != -1) {
        head[node] = nxt[child];
        stack.push_back(child);
      } else {
        post[k++] = node;
        stack.pop_back();
      }
    }
  }
}

// ------------------------------------------------------------ colcounts
// Gilbert–Ng–Peyton skeleton/leaf counting with path-halving LCA on a
// postordered symmetric pattern (parent[j] > j for non-roots).
void slu_colcounts(int64_t n, const int64_t* indptr, const int64_t* indices,
                   const int64_t* parent, int64_t* colcount) {
  std::vector<int64_t> first(n, -1), maxfirst(n, -1), prevleaf(n, -1),
      ancestor(n), delta(n, 0);
  for (int64_t j = 0; j < n; ++j) ancestor[j] = j;
  for (int64_t k = 0; k < n; ++k) {
    int64_t j = k;
    delta[j] = (first[j] == -1) ? 1 : 0;
    while (j != -1 && first[j] == -1) { first[j] = k; j = parent[j]; }
  }
  auto find = [&](int64_t q) {
    while (ancestor[q] != q) {
      ancestor[q] = ancestor[ancestor[q]];
      q = ancestor[q];
    }
    return q;
  };
  for (int64_t k = 0; k < n; ++k) {
    int64_t j = k, p = parent[j];
    if (p != -1) delta[p] -= 1;
    for (int64_t t = indptr[j]; t < indptr[j + 1]; ++t) {
      int64_t i = indices[t];
      if (i <= j) continue;
      if (first[j] > maxfirst[i]) {
        delta[j] += 1;
        maxfirst[i] = first[j];
        int64_t pl = prevleaf[i];
        if (pl != -1) delta[find(pl)] -= 1;
        prevleaf[i] = j;
      }
    }
    if (p != -1) ancestor[j] = p;
  }
  for (int64_t j = 0; j < n; ++j) colcount[j] = delta[j];
  for (int64_t j = 0; j < n; ++j) {
    int64_t p = parent[j];
    if (p != -1) colcount[p] += colcount[j];
  }
}

// -------------------------------------------------------------- mdorder
// Quotient-graph minimum degree with exact external degrees,
// supervariable (mass) elimination and element absorption — the same
// algorithm family as the reference's genmmd (SRC/mmd.c).  Eliminated
// pivots become "elements" whose variable lists stand in for the fill
// clique, so fill edges are never materialized and memory stays O(nnz).
// `order[k]` = k-th pivot in original labels.  Returns n on success.
int64_t slu_mdorder(int64_t n, const int64_t* indptr,
                    const int64_t* indices, int64_t* order) {
  if (n == 0) return 0;
  std::vector<std::vector<int64_t>> adj(n), els(n), members(n);
  for (int64_t j = 0; j < n; ++j) {
    adj[j].reserve(indptr[j + 1] - indptr[j]);
    for (int64_t p = indptr[j]; p < indptr[j + 1]; ++p) {
      int64_t i = indices[p];
      if (i != j) adj[j].push_back(i);
    }
    members[j].push_back(j);
  }
  std::vector<std::vector<int64_t>> elem_vars;  // element -> member vars
  std::vector<int64_t> nv(n, 1);                // supervariable weights
  std::vector<int64_t> mark(n, -1), degree(n);
  std::vector<char> dead(n, 0);                 // eliminated or absorbed
  int64_t stamp = 0;

  // exact weighted external degree of u via marker scan
  auto exact_degree = [&](int64_t u) -> int64_t {
    ++stamp;
    mark[u] = stamp;
    int64_t deg = 0;
    for (int64_t w2 : adj[u])
      if (!dead[w2] && mark[w2] != stamp) { mark[w2] = stamp; deg += nv[w2]; }
    for (int64_t e : els[u])
      for (int64_t w2 : elem_vars[e])
        if (!dead[w2] && mark[w2] != stamp) { mark[w2] = stamp; deg += nv[w2]; }
    return deg;
  };

  using HeapItem = std::pair<int64_t, int64_t>;  // (degree, var)
  std::priority_queue<HeapItem, std::vector<HeapItem>,
                      std::greater<HeapItem>> heap;
  for (int64_t j = 0; j < n; ++j) {
    degree[j] = exact_degree(j);
    heap.push({degree[j], j});
  }

  int64_t k = 0;
  std::vector<int64_t> pivot_nbrs;
  std::vector<int64_t> absorbed_stamp;  // element -> pivot count when absorbed
  int64_t pivot_count = 0;
  while (k < n) {
    int64_t v = -1;
    while (!heap.empty()) {
      auto [d, cand] = heap.top();
      heap.pop();
      if (!dead[cand] && d == degree[cand]) { v = cand; break; }
    }
    if (v == -1) {  // disconnected stragglers
      for (int64_t j = 0; j < n; ++j)
        if (!dead[j]) {
          dead[j] = 1;
          for (int64_t m : members[j]) order[k++] = m;
        }
      break;
    }

    // the new element's variable set = v's current neighborhood
    ++stamp;
    mark[v] = stamp;
    pivot_nbrs.clear();
    for (int64_t w2 : adj[v])
      if (!dead[w2] && mark[w2] != stamp) {
        mark[w2] = stamp;
        pivot_nbrs.push_back(w2);
      }
    for (int64_t e : els[v])
      for (int64_t w2 : elem_vars[e])
        if (!dead[w2] && w2 != v && mark[w2] != stamp) {
          mark[w2] = stamp;
          pivot_nbrs.push_back(w2);
        }

    int64_t enew = (int64_t)elem_vars.size();
    elem_vars.push_back(pivot_nbrs);
    dead[v] = 1;
    for (int64_t m : members[v]) order[k++] = m;

    // neighbor cleanup: drop covered variable adjacency, absorb v's
    // elements, attach enew.  mark currently flags members of enew ∪ {v}.
    ++pivot_count;
    absorbed_stamp.resize(elem_vars.size(), 0);
    for (int64_t e : els[v]) absorbed_stamp[e] = pivot_count;
    for (int64_t u : pivot_nbrs) {
      auto& au = adj[u];
      size_t t = 0;
      for (int64_t w2 : au) {
        if (dead[w2] || w2 == v) continue;
        if (mark[w2] == stamp) continue;  // covered by enew
        au[t++] = w2;
      }
      au.resize(t);
      auto& eu = els[u];
      size_t te = 0;
      for (int64_t e : eu)
        if (absorbed_stamp[e] != pivot_count) eu[te++] = e;
      eu.resize(te);
      eu.push_back(enew);
    }
    els[v].clear();
    adj[v].clear();

    // supervariable detection among enew's members: hash adjacency,
    // verify exactly, merge u2 into u1 (weights and members add)
    if (pivot_nbrs.size() > 1) {
      std::vector<std::pair<uint64_t, int64_t>> sig;
      sig.reserve(pivot_nbrs.size());
      for (int64_t u : pivot_nbrs) {
        if (dead[u]) continue;
        uint64_t h = 1469598103934665603ull;
        for (int64_t w2 : adj[u])
          if (!dead[w2]) h += (uint64_t)w2 * 1099511628211ull;
        std::vector<int64_t> es = els[u];
        std::sort(es.begin(), es.end());
        for (int64_t e : es)
          h ^= ((uint64_t)e + 0x9e3779b97f4a7c15ull) * 0xff51afd7ed558ccdull;
        sig.push_back({h, u});
      }
      std::sort(sig.begin(), sig.end());
      for (size_t a2 = 0; a2 < sig.size(); ++a2) {
        int64_t u1 = sig[a2].second;
        if (dead[u1]) continue;
        for (size_t b2 = a2 + 1;
             b2 < sig.size() && sig[b2].first == sig[a2].first; ++b2) {
          int64_t u2 = sig[b2].second;
          if (dead[u2]) continue;
          // exact test: adj sets equal modulo {u1,u2}, element sets equal
          ++stamp;
          int64_t c1 = 0;
          for (int64_t w2 : adj[u1])
            if (!dead[w2] && w2 != u2) { mark[w2] = stamp; ++c1; }
          bool same = true;
          int64_t c2 = 0;
          for (int64_t w2 : adj[u2]) {
            if (dead[w2] || w2 == u1) continue;
            ++c2;
            if (mark[w2] != stamp) { same = false; break; }
          }
          if (!same || c1 != c2) continue;
          std::vector<int64_t> e1 = els[u1], e2 = els[u2];
          std::sort(e1.begin(), e1.end());
          std::sort(e2.begin(), e2.end());
          if (e1 != e2) continue;
          nv[u1] += nv[u2];
          dead[u2] = 1;
          members[u1].insert(members[u1].end(), members[u2].begin(),
                             members[u2].end());
          members[u2].clear();
          adj[u2].clear();
          els[u2].clear();
        }
      }
    }

    // refresh degrees of the element's surviving members
    for (int64_t u : pivot_nbrs) {
      if (dead[u]) continue;
      degree[u] = exact_degree(u);
      heap.push({degree[u], u});
    }
  }
  return k;
}

// ---------------------------------------------------------------- mc64
// Maximum-product-of-diagonal bipartite matching (MC64 job=5) by
// shortest augmenting paths with dual potentials (the Duff–Koster
// algorithm; also the sparse Jonker–Volgenant assignment).  Input is
// CSC of the n×n pattern with |a_ij| values (zeros allowed — skipped).
// Edge weight w(i,j) = log(cmax_j / |a_ij|) ≥ 0; a minimum-weight
// perfect matching maximizes the product of matched magnitudes.
//
// Outputs: rowperm[i] = matched column of row i (row i moves to
// position rowperm[i]); duals u (rows), v (cols) satisfying
// w(i,j) − u_i − v_j ≥ 0 with equality on matched edges, from which
// the MC64 job=5 scalings are R_i = exp(u_i), C_j = exp(v_j)/cmax_j.
// A search resets only the rows it touched: no length-n refill per
// augmentation (a saddle point's zero block leaves a third of the
// columns to search).  `work`, where not null, receives {columns
// searched, rows finalized by all searches, edges scanned by all
// searches}: counts of work, for the tests' bound.
// Returns 0 on success, -1 if structurally singular.
int64_t slu_mc64_counted(int64_t n, const int64_t* colptr,
                         const int64_t* rowind, const double* absval,
                         int64_t* rowperm, double* u, double* v,
                         int64_t* work) {
  const double INF = std::numeric_limits<double>::infinity();
  std::vector<double> w(colptr[n]);
  std::vector<double> cmax(n, 0.0);
  for (int64_t j = 0; j < n; ++j)
    for (int64_t p = colptr[j]; p < colptr[j + 1]; ++p)
      if (absval[p] > cmax[j]) cmax[j] = absval[p];
  for (int64_t j = 0; j < n; ++j) {
    if (cmax[j] <= 0.0) return -1;  // structurally empty column
    double lc = std::log(cmax[j]);
    for (int64_t p = colptr[j]; p < colptr[j + 1]; ++p)
      w[p] = (absval[p] > 0.0) ? lc - std::log(absval[p]) : INF;
  }

  std::vector<int64_t> match_row(n, -1);  // row -> col
  std::vector<int64_t> match_col(n, -1);  // col -> row
  for (int64_t i = 0; i < n; ++i) u[i] = INF;
  for (int64_t j = 0; j < n; ++j) v[j] = 0.0;
  // feasible start: u_i = cheapest incident edge (then w − u − 0 ≥ 0)
  for (int64_t j = 0; j < n; ++j)
    for (int64_t p = colptr[j]; p < colptr[j + 1]; ++p)
      if (w[p] < u[rowind[p]]) u[rowind[p]] = w[p];
  for (int64_t i = 0; i < n; ++i)
    if (u[i] == INF) return -1;  // structurally empty row

  // cheap assignment pass on tight edges
  for (int64_t j = 0; j < n; ++j)
    for (int64_t p = colptr[j]; p < colptr[j + 1]; ++p) {
      int64_t i = rowind[p];
      if (match_row[i] == -1 && w[p] - u[i] <= 0.0) {
        match_row[i] = j;
        match_col[j] = i;
        break;
      }
    }

  std::vector<double> dist(n, INF);
  std::vector<int64_t> prev_col(n);  // row -> column it was reached from
  std::vector<char> done(n, 0);
  std::vector<int64_t> done_rows, touched;
  int64_t searches = 0, finalized = 0, scanned = 0;
  using QI = std::pair<double, int64_t>;  // (dist, row)
  for (int64_t j0 = 0; j0 < n; ++j0) {
    if (match_col[j0] != -1) continue;
    ++searches;
    for (int64_t i : touched) { dist[i] = INF; done[i] = 0; }
    touched.clear();
    done_rows.clear();
    std::priority_queue<QI, std::vector<QI>, std::greater<QI>> pq;
    for (int64_t p = colptr[j0]; p < colptr[j0 + 1]; ++p) {
      int64_t i = rowind[p];
      double d = w[p] - v[j0] - u[i];
      ++scanned;
      if (d < dist[i]) {
        if (dist[i] == INF) touched.push_back(i);
        dist[i] = d;
        prev_col[i] = j0;
        pq.push({d, i});
      }
    }
    double lsp = INF;
    int64_t isp = -1;
    while (!pq.empty()) {
      auto [d, i] = pq.top();
      pq.pop();
      if (done[i] || d > dist[i]) continue;
      done[i] = 1;
      done_rows.push_back(i);
      int64_t jm = match_row[i];
      if (jm == -1) { lsp = d; isp = i; break; }
      for (int64_t p = colptr[jm]; p < colptr[jm + 1]; ++p) {
        int64_t i2 = rowind[p];
        ++scanned;
        if (done[i2] || w[p] == INF) continue;
        double d2 = d + (w[p] - v[jm] - u[i2]);
        if (d2 < dist[i2]) {
          if (dist[i2] == INF) touched.push_back(i2);
          dist[i2] = d2;
          prev_col[i2] = jm;
          pq.push({d2, i2});
        }
      }
    }
    finalized += static_cast<int64_t>(done_rows.size());
    if (isp == -1) return -1;  // no augmenting path: singular

    // dual update on finalized rows keeps feasibility (d ≤ lsp there)
    for (int64_t i : done_rows) u[i] += dist[i] - lsp;
    // augment along the prev_col chain
    int64_t i = isp;
    while (true) {
      int64_t j = prev_col[i];
      int64_t iold = match_col[j];
      match_col[j] = i;
      match_row[i] = j;
      if (j == j0) break;
      i = iold;
    }
    // retighten matched edges of rows whose dual moved
    for (int64_t i2 : done_rows) {
      int64_t j = match_row[i2];
      if (j == -1) continue;
      for (int64_t p = colptr[j]; p < colptr[j + 1]; ++p)
        if (rowind[p] == i2) { v[j] = w[p] - u[i2]; break; }
    }
  }
  for (int64_t i = 0; i < n; ++i) rowperm[i] = match_row[i];
  if (work) { work[0] = searches; work[1] = finalized; work[2] = scanned; }
  return 0;
}

int64_t slu_mc64(int64_t n, const int64_t* colptr, const int64_t* rowind,
                 const double* absval, int64_t* rowperm, double* u,
                 double* v) {
  return slu_mc64_counted(n, colptr, rowind, absval, rowperm, u, v,
                          nullptr);
}

// ---------------------------------------------------------------- hwpm
// Approximate heavy-weight perfect matching — the parallel
// LargeDiag_HWPM slot (reference SRC/d_c2cpp_GetHWPM.cpp →
// dHWPM_CombBLAS.hpp:60, which delegates to CombBLAS's distributed
// AWPM).  Shared-memory redesign, not a port:
//
//   1. locally-dominant parallel greedy matching on the weights
//      w(i,j) = log|a_ij| − log cmax_j: threaded rounds where every
//      free row proposes its best still-free column and each column
//      atomically accepts the heaviest proposal (a ≥1/2-approximation
//      of the maximum-weight matching, like AWPM's dominant-edge
//      phase);
//   2. completion to a PERFECT matching by augmenting paths over the
//      pattern, trying heavy edges first (HWPM also trades diagonal
//      weight for perfection — static pivoting needs a structurally
//      full diagonal above all).
//
// Produces the permutation only — no dual scalings — matching the
// reference HWPM contract (MC64 job=5 is the scaling-producing path).
// Exact zeros are treated as structurally absent, as in slu_mc64.
// nthreads ≤ 0 → hardware concurrency.  Returns 0, or -1 when no
// perfect matching exists (structurally singular).
int64_t slu_hwpm(int64_t n, const int64_t* colptr, const int64_t* rowind,
                 const double* absval, int64_t nthreads,
                 int64_t* rowperm) {
  const double NEG_INF = -std::numeric_limits<double>::infinity();
  const int64_t nnz = colptr[n];
  // the proposal key packs the row id into 32 bits; beyond that the
  // accept phase would decode the wrong row (caller falls back to the
  // exact matching — unreachable in practice)
  if (n >= ((int64_t)1 << 32)) return -2;
  if (nthreads <= 0) {
    unsigned hc = std::thread::hardware_concurrency();
    nthreads = hc ? (int64_t)hc : 1;
  }
  if (n < (int64_t)1 << 13) nthreads = 1;  // thread spawn not worth it

  std::vector<double> cmax(n, 0.0);
  for (int64_t j = 0; j < n; ++j)
    for (int64_t p = colptr[j]; p < colptr[j + 1]; ++p)
      if (absval[p] > cmax[j]) cmax[j] = absval[p];
  for (int64_t j = 0; j < n; ++j)
    if (cmax[j] <= 0.0) return -1;  // structurally empty column

  // row-major adjacency (transpose of the CSC input) with weights
  std::vector<int64_t> rptr(n + 1, 0), rcol(nnz);
  std::vector<double> rw(nnz);
  for (int64_t p = 0; p < nnz; ++p) rptr[rowind[p] + 1]++;
  for (int64_t i = 0; i < n; ++i) rptr[i + 1] += rptr[i];
  {
    std::vector<int64_t> cur(rptr.begin(), rptr.end() - 1);
    for (int64_t j = 0; j < n; ++j) {
      double lc = std::log(cmax[j]);
      for (int64_t p = colptr[j]; p < colptr[j + 1]; ++p) {
        int64_t i = rowind[p], q = cur[i]++;
        rcol[q] = j;
        rw[q] = absval[p] > 0.0 ? std::log(absval[p]) - lc : NEG_INF;
      }
    }
  }

  // per-row candidates sorted heaviest-first (embarrassingly parallel)
  auto sort_span = [&](int64_t lo, int64_t hi) {
    std::vector<int64_t> ord;
    std::vector<int64_t> tc;
    std::vector<double> tw;
    for (int64_t i = lo; i < hi; ++i) {
      int64_t b = rptr[i], e = rptr[i + 1], m = e - b;
      if (m <= 1) continue;
      ord.resize(m);
      std::iota(ord.begin(), ord.end(), (int64_t)0);
      std::sort(ord.begin(), ord.end(), [&](int64_t x, int64_t y) {
        return rw[b + x] > rw[b + y];
      });
      tc.assign(rcol.begin() + b, rcol.begin() + e);
      tw.assign(rw.begin() + b, rw.begin() + e);
      for (int64_t k = 0; k < m; ++k) {
        rcol[b + k] = tc[ord[k]];
        rw[b + k] = tw[ord[k]];
      }
    }
  };
  if (nthreads > 1) {
    std::vector<std::thread> ts;
    int64_t chunk = (n + nthreads - 1) / nthreads;
    for (int64_t t = 0; t < nthreads; ++t)
      ts.emplace_back(sort_span, t * chunk,
                      std::min(n, (t + 1) * chunk));
    for (auto& t : ts) t.join();
  } else {
    sort_span(0, n);
  }

  // ---- phase 1: locally-dominant greedy (propose / accept rounds)
  // proposal key packs (order-preserving f32 of the weight, ~row) so
  // one 64-bit CAS-max resolves "heaviest proposal wins, smallest row
  // breaks ties"; f32 rounding only blurs near-equal-weight ties,
  // fine for an approximate matching.
  auto prop_key = [](double wgt, int64_t row) -> uint64_t {
    float f = (float)wgt;
    uint32_t bits;
    std::memcpy(&bits, &f, 4);
    bits = (bits & 0x80000000u) ? ~bits : (bits | 0x80000000u);
    return ((uint64_t)bits << 32) | (uint32_t)(~(uint32_t)row);
  };
  std::vector<int64_t> match_row(n, -1), match_col(n, -1);
  std::vector<int64_t> ptr(rptr.begin(), rptr.end() - 1);
  std::vector<std::atomic<uint64_t>> best(n);
  for (auto& b : best) b.store(0, std::memory_order_relaxed);
  std::vector<int64_t> frees(n);
  std::iota(frees.begin(), frees.end(), (int64_t)0);
  std::vector<int64_t> touched;  // columns proposed this round

  while (!frees.empty()) {
    touched.clear();
    // propose (parallel over free rows)
    std::atomic<int64_t> widx{0};
    std::vector<std::vector<int64_t>> touched_t(nthreads);
    auto propose = [&](int64_t t) {
      int64_t i;
      while ((i = widx.fetch_add(1)) < (int64_t)frees.size()) {
        int64_t r = frees[i];
        int64_t e = rptr[r + 1];
        while (ptr[r] < e && (match_col[rcol[ptr[r]]] != -1 ||
                              rw[ptr[r]] == NEG_INF))
          ++ptr[r];
        if (ptr[r] >= e) continue;  // exhausted: completion phase
        int64_t j = rcol[ptr[r]];
        uint64_t key = prop_key(rw[ptr[r]], r);
        uint64_t cur = best[j].load(std::memory_order_relaxed);
        bool first = (cur == 0);
        while (cur < key && !best[j].compare_exchange_weak(
                   cur, key, std::memory_order_relaxed)) {}
        if (first) touched_t[t].push_back(j);
      }
    };
    if (nthreads > 1) {
      std::vector<std::thread> ts;
      for (int64_t t = 0; t < nthreads; ++t)
        ts.emplace_back(propose, t);
      for (auto& t : ts) t.join();
    } else {
      propose(0);
    }
    // accept: the winning row of each touched column matches it
    bool any = false;
    std::vector<int64_t> next_free;
    next_free.reserve(frees.size());
    for (auto& tt : touched_t)
      for (int64_t j : tt) touched.push_back(j);
    for (int64_t j : touched) {
      uint64_t key = best[j].exchange(0, std::memory_order_relaxed);
      if (key == 0 || match_col[j] != -1) continue;
      int64_t r = (int64_t)(uint32_t)~((uint32_t)(key & 0xffffffffu));
      if (match_row[r] != -1) continue;
      match_row[r] = j;
      match_col[j] = r;
      any = true;
    }
    for (int64_t r : frees)
      if (match_row[r] == -1 && ptr[r] < rptr[r + 1])
        next_free.push_back(r);
    frees.swap(next_free);
    if (!any && !frees.empty()) {
      // every remaining proposal lost to an already-matched column;
      // pointers advanced, so progress continues — but guard against
      // a stall where all rows are exhausted
      bool progress = false;
      for (int64_t r : frees)
        if (ptr[r] < rptr[r + 1]) { progress = true; break; }
      if (!progress) break;
    }
  }

  // ---- phase 2: completion to a perfect matching by Hopcroft–Karp
  // (BFS-layered phases of vertex-disjoint shortest augmenting paths,
  // O(E·√V); heavy edges are still tried first within a layer thanks
  // to the candidate sort).  Augmentation may rotate some greedy
  // pairs — perfection over weight, the same trade the reference's
  // HWPM completion makes (static pivoting needs a structurally full
  // diagonal above all).
  const int64_t INF64 = std::numeric_limits<int64_t>::max();
  std::vector<int64_t> dist(n), bfs_q(n), stk_row;
  std::vector<int64_t> dfs_ptr(n);
  while (true) {
    // BFS from all free rows over alternating edges
    int64_t qh = 0, qt = 0;
    std::fill(dist.begin(), dist.end(), INF64);
    for (int64_t r = 0; r < n; ++r)
      if (match_row[r] == -1) {
        dist[r] = 0;
        bfs_q[qt++] = r;
      }
    if (qt == 0) break;  // already perfect
    bool reachable = false;
    while (qh < qt) {
      int64_t r = bfs_q[qh++];
      for (int64_t p = rptr[r]; p < rptr[r + 1]; ++p) {
        if (rw[p] == NEG_INF) continue;
        int64_t r2 = match_col[rcol[p]];
        if (r2 == -1) {
          reachable = true;
        } else if (dist[r2] == INF64) {
          dist[r2] = dist[r] + 1;
          bfs_q[qt++] = r2;
        }
      }
    }
    if (!reachable) return -1;  // free rows but no augmenting path
    // layered DFS: vertex-disjoint augmenting paths
    std::copy(rptr.begin(), rptr.end() - 1, dfs_ptr.begin());
    for (int64_t r0 = 0; r0 < n; ++r0) {
      if (match_row[r0] != -1) continue;
      stk_row.assign(1, r0);
      while (!stk_row.empty()) {
        int64_t r = stk_row.back();
        int64_t& p = dfs_ptr[r];
        if (p >= rptr[r + 1]) {
          dist[r] = INF64;  // dead end: prune for this phase
          stk_row.pop_back();
          continue;
        }
        int64_t q = p++;
        if (rw[q] == NEG_INF) continue;
        int64_t j = rcol[q];
        int64_t r2 = match_col[j];
        if (r2 == -1) {
          // augment along the stack: stack rows are the path
          int64_t jj = j;
          for (int64_t d = (int64_t)stk_row.size() - 1; d >= 0; --d) {
            int64_t rr = stk_row[d];
            int64_t prevj = match_row[rr];
            match_row[rr] = jj;
            match_col[jj] = rr;
            jj = prevj;
          }
          for (int64_t rr : stk_row) dist[rr] = INF64;  // used up
          stk_row.clear();
        } else if (dist[r2] == dist[r] + 1) {
          stk_row.push_back(r2);
        }
      }
    }
  }
  for (int64_t i = 0; i < n; ++i) rowperm[i] = match_row[i];
  return 0;
}

// ---------------------------------------------------------- supernodes
// Supernode partition: relaxed leaf subtrees + fundamental supernodes
// (reference relax_snode / sp_ienv(2); mirrors
// superlu_dist_tpu/plan/supernodes.py find_supernodes step for step —
// the Python version is the bit-identical oracle).  Returns nsuper;
// fills supno (n), xsup (first ns+1 slots), sparent (first ns slots).
int64_t slu_supernodes(int64_t n, const int64_t* parent,
                       const int64_t* colcount, int64_t relax,
                       int64_t max_super, int64_t* supno,
                       int64_t* xsup, int64_t* sparent) {
  if (n == 0) { xsup[0] = 0; return 0; }
  relax = std::max<int64_t>(1, std::min(relax, max_super));
  std::vector<int64_t> size(n, 1);
  for (int64_t j = 0; j < n; ++j)
    if (parent[j] != -1) size[parent[j]] += size[j];
  int64_t ns = 0, j = 0;
  while (j < n) {
    // maximal relaxed subtree containing j (postorder contiguity)
    int64_t r = j;
    while (parent[r] != -1 && size[parent[r]] <= relax) r = parent[r];
    bool snode_root = size[r] <= relax &&
                      (parent[r] == -1 || size[parent[r]] > relax);
    if (snode_root) {
      int64_t first = r - size[r] + 1;
      int64_t w = r - first + 1;
      int64_t start = first;
      while (w > 0) {                 // split over-wide relaxed snodes
        int64_t take = std::min(w, max_super);
        xsup[ns] = start;
        for (int64_t t = start; t < start + take; ++t) supno[t] = ns;
        ++ns;
        start += take;
        w -= take;
      }
      j = r + 1;
      continue;
    }
    // fundamental run starting at j (the snode_root clause of the
    // oracle's loop condition is implied by size[k] > relax)
    xsup[ns] = j;
    supno[j] = ns;
    int64_t k = j + 1;
    while (k < n && parent[k - 1] == k &&
           colcount[k - 1] == colcount[k] + 1 &&
           (k - j) < max_super && size[k] > relax) {
      supno[k] = ns;
      ++k;
    }
    ++ns;
    j = k;
  }
  xsup[ns] = n;
  for (int64_t s = 0; s < ns; ++s) {
    int64_t p = parent[xsup[s + 1] - 1];
    sparent[s] = (p == -1) ? -1 : supno[p];
  }
  return ns;
}

// ------------------------------------------- nested dissection ordering
// BFS level-set bisection nested dissection, the METIS_AT_PLUS_A /
// ParMETIS slot of get_perm_c_dist (reference SRC/get_perm_c.c:91,489;
// SRC/get_perm_c_parmetis.c:255).  Mirrors the numpy implementation in
// superlu_dist_tpu/plan/nested.py step for step (same BFS level sets,
// same pseudo-peripheral restarts, same median split, same emit order),
// so the two produce IDENTICAL orderings — the Python version is the
// test oracle.  The two recursion halves write disjoint output ranges,
// so the top recursion levels fan out over std::thread (the
// process-parallel-ordering analog of ParMETIS).

}  // extern "C" — the ND internals are C++-linkage

namespace nd {

struct Graph {
  std::vector<int64_t> ip, ix, labels;
};

// BFS from src on local graph of k nodes; fills level; returns
// eccentricity (max level reached)
static int64_t bfs(const Graph& g, int64_t k, int64_t src,
                   std::vector<int64_t>& level,
                   std::vector<int64_t>& frontier,
                   std::vector<int64_t>& next) {
  std::fill(level.begin(), level.begin() + k, -1);
  level[src] = 0;
  frontier.clear();
  frontier.push_back(src);
  int64_t lev = 0;
  while (!frontier.empty()) {
    ++lev;
    next.clear();
    for (int64_t u : frontier)
      for (int64_t p = g.ip[u]; p < g.ip[u + 1]; ++p) {
        int64_t v = g.ix[p];
        if (level[v] == -1) { level[v] = lev; next.push_back(v); }
      }
    frontier.swap(next);
  }
  int64_t ecc = 0;
  for (int64_t i = 0; i < k; ++i) ecc = std::max(ecc, level[i]);
  return ecc;
}

// induced subgraph of the sorted local-node list `part`
static Graph subgraph(const Graph& g, const std::vector<int64_t>& part,
                      std::vector<int64_t>& posmap) {
  Graph s;
  int64_t m = (int64_t)part.size();
  for (int64_t i = 0; i < m; ++i) posmap[part[i]] = i;
  s.ip.resize(m + 1);
  s.ip[0] = 0;
  int64_t nnz = 0;
  for (int64_t i = 0; i < m; ++i) {
    int64_t u = part[i];
    for (int64_t p = g.ip[u]; p < g.ip[u + 1]; ++p)
      if (posmap[g.ix[p]] >= 0) ++nnz;
    s.ip[i + 1] = nnz;
  }
  s.ix.resize(nnz);
  int64_t c = 0;
  for (int64_t i = 0; i < m; ++i) {
    int64_t u = part[i];
    for (int64_t p = g.ip[u]; p < g.ip[u + 1]; ++p) {
      int64_t v = posmap[g.ix[p]];
      if (v >= 0) s.ix[c++] = v;
    }
  }
  s.labels.resize(m);
  for (int64_t i = 0; i < m; ++i) s.labels[i] = g.labels[part[i]];
  for (int64_t i = 0; i < m; ++i) posmap[part[i]] = -1;  // reset
  return s;
}

// Iterative driver with an explicit work list — NO recursion per
// component or per bisection level (a graph with 10^5 components or a
// path graph must not overflow the C stack).  The only recursion is
// the std::thread fan-out, bounded by par_depth ≤ log2(nthreads).
static void solve(Graph g0, int64_t* out, int64_t pos0, int64_t leaf,
                  int par_depth) {
  std::vector<std::pair<Graph, int64_t>> todo;
  todo.emplace_back(std::move(g0), pos0);
  std::vector<std::thread> spawned;
  std::vector<int64_t> level, frontier, next, posmap, a, b, sep;

  while (!todo.empty()) {
    Graph g = std::move(todo.back().first);
    int64_t pos = todo.back().second;
    todo.pop_back();
    for (;;) {
      int64_t k = (int64_t)g.labels.size();
      if (k <= leaf) {
        std::memcpy(out + pos, g.labels.data(), k * sizeof(int64_t));
        break;
      }
      level.assign(k, -1);
      frontier.clear();
      next.clear();
      int64_t src = 0, last_ecc = -1;
      int64_t ecc = bfs(g, k, src, level, frontier, next);
      for (int it = 0; it < 4; ++it) {
        if (ecc <= last_ecc) break;
        last_ecc = ecc;
        for (int64_t i = 0; i < k; ++i)
          if (level[i] == ecc) { src = i; break; }
        ecc = bfs(g, k, src, level, frontier, next);
      }
      posmap.assign(k, -1);
      a.clear();
      b.clear();
      bool disconnected = false;
      for (int64_t i = 0; i < k; ++i)
        if (level[i] < 0) { disconnected = true; break; }
      if (disconnected) {
        // label ALL components in one O(nnz) pass (ascending seed
        // order = the oracle's peel order, so output is identical,
        // without the oracle's O(#components²) peel cost)
        std::vector<int64_t> comp(k, -1);
        std::vector<std::vector<int64_t>> parts;
        for (int64_t i = 0; i < k; ++i) {
          if (comp[i] >= 0) continue;
          int64_t c = (int64_t)parts.size();
          parts.emplace_back();
          comp[i] = c;
          frontier.clear();
          frontier.push_back(i);
          parts[c].push_back(i);
          while (!frontier.empty()) {
            next.clear();
            for (int64_t u : frontier)
              for (int64_t p2 = g.ip[u]; p2 < g.ip[u + 1]; ++p2) {
                int64_t v = g.ix[p2];
                if (comp[v] < 0) {
                  comp[v] = c;
                  parts[c].push_back(v);
                  next.push_back(v);
                }
              }
            frontier.swap(next);
          }
          std::sort(parts[c].begin(), parts[c].end());
        }
        Graph first;
        int64_t off = pos;
        for (size_t c = 0; c < parts.size(); ++c) {
          Graph s = subgraph(g, parts[c], posmap);
          if (c == 0)
            first = std::move(s);
          else
            todo.emplace_back(std::move(s), off);
          off += (int64_t)parts[c].size();
        }
        g = std::move(first);         // component of node 0, at `pos`
        continue;
      }
      int64_t maxlev = ecc;
      if (maxlev < 2) {
        std::memcpy(out + pos, g.labels.data(), k * sizeof(int64_t));
        break;
      }
      // median split of the level structure (first cum ≥ k/2, clipped)
      std::vector<int64_t> counts(maxlev + 1, 0);
      for (int64_t i = 0; i < k; ++i) ++counts[level[i]];
      int64_t split = maxlev - 1, cum = 0;
      for (int64_t l = 0; l <= maxlev; ++l) {
        cum += counts[l];
        if (2 * cum >= k) { split = l; break; }
      }
      split = std::max<int64_t>(1, std::min(split, maxlev - 1));
      sep.clear();
      for (int64_t i = 0; i < k; ++i) {
        if (level[i] < split) a.push_back(i);
        else if (level[i] > split) b.push_back(i);
        else sep.push_back(i);
      }
      Graph left = subgraph(g, a, posmap);
      Graph right = subgraph(g, b, posmap);
      int64_t nl = (int64_t)a.size(), nr = (int64_t)b.size();
      for (size_t i = 0; i < sep.size(); ++i)
        out[pos + nl + nr + (int64_t)i] = g.labels[sep[i]];
      g = Graph();
      if (par_depth > 0 && nl > leaf && nr > leaf) {
        // bounded recursion: ≤ log2(nthreads) nested solve frames
        spawned.emplace_back(
            [r = std::move(right), out, p = pos + nl, leaf,
             par_depth]() mutable {
              solve(std::move(r), out, p, leaf, par_depth - 1);
            });
        --par_depth;
      } else {
        todo.emplace_back(std::move(right), pos + nl);
      }
      g = std::move(left);            // keep going at `pos`
    }
  }
  for (auto& t : spawned) t.join();
}

}  // namespace nd

extern "C" {

int64_t slu_ndorder(int64_t n, const int64_t* indptr,
                    const int64_t* indices, int64_t leaf,
                    int64_t nthreads, int64_t* out) {
  nd::Graph g;
  g.ip.assign(indptr, indptr + n + 1);
  g.ix.assign(indices, indices + indptr[n]);
  g.labels.resize(n);
  for (int64_t i = 0; i < n; ++i) g.labels[i] = i;
  int par_depth = 0;
  while ((int64_t(1) << (par_depth + 1)) <= nthreads) ++par_depth;
  nd::solve(std::move(g), out, 0, leaf, par_depth);
  return n;
}

// ------------------------------------------------------------- symbfact
// Supernodal symbolic factorization: per-supernode union pass over the
// postordered supernodal etree (the reference's symbfact computes the
// same structures column-by-column, SRC/symbfact.c:81; supernode
// granularity here matches superlu_dist_tpu/plan/symbolic.py).
// Handle-based: create → query sizes → copy out → free.
struct SymbHandle {
  std::vector<std::vector<int64_t>> structs;
  int64_t total = 0;
};

void* slu_symbfact_create_par(int64_t n, const int64_t* b_indptr,
                              const int64_t* b_indices, int64_t nsuper,
                              const int64_t* xsup,
                              const int64_t* sparent, int64_t nthreads);

void* slu_symbfact_create(int64_t n, const int64_t* b_indptr,
                          const int64_t* b_indices, int64_t nsuper,
                          const int64_t* xsup, const int64_t* sparent) {
  // one union-pass implementation: the parallel variant with one
  // worker IS the serial pass (every level takes the serial branch)
  return slu_symbfact_create_par(n, b_indptr, b_indices, nsuper, xsup,
                                 sparent, 1);
}

// Parallel supernodal symbolic factorization: level-synchronous over
// the supernodal etree — all supernodes at one level depend only on
// children at lower levels, so each level is an embarrassingly
// parallel batch.  This is the shared-memory analog of the
// reference's parallel symbfact_dist (SRC/psymbfact.c:150: its
// domain_symbfact phase = the low, wide levels here; its
// interLvl/intraLvl phases = the narrow top levels, which this
// version simply runs on one thread since they hold a tiny fraction
// of the work).  Output is bit-identical to slu_symbfact_create.
void* slu_symbfact_create_par(int64_t n, const int64_t* b_indptr,
                              const int64_t* b_indices, int64_t nsuper,
                              const int64_t* xsup,
                              const int64_t* sparent,
                              int64_t nthreads) {
  auto* h = new SymbHandle();
  h->structs.resize(nsuper);
  std::vector<std::vector<int64_t>> children(nsuper);
  std::vector<int64_t> level(nsuper, 0);
  int64_t maxlev = 0;
  for (int64_t s = 0; s < nsuper; ++s) {  // postorder: s < sparent[s]
    int64_t p = sparent[s];
    if (p != -1) {
      children[p].push_back(s);
      if (level[s] + 1 > level[p]) level[p] = level[s] + 1;
    }
    if (level[s] > maxlev) maxlev = level[s];
  }
  std::vector<std::vector<int64_t>> bylevel(maxlev + 1);
  for (int64_t s = 0; s < nsuper; ++s) bylevel[level[s]].push_back(s);

  int64_t nt = std::max<int64_t>(
      1, std::min<int64_t>(nthreads, 16));
  // per-thread mark scratch, grown lazily to the widest parallel
  // level's worker count; mark values are supernode ids, unique
  // across the whole run, so scratch is reusable across levels
  std::vector<std::vector<int64_t>> marks;
  auto ensure_marks = [&](int64_t use) {
    while ((int64_t)marks.size() < use)
      marks.emplace_back(n, -1);
  };

  auto do_sup = [&](int64_t s, std::vector<int64_t>& mark,
                    std::vector<int64_t>& rows) {
    int64_t last = xsup[s + 1] - 1;
    rows.clear();
    for (int64_t j = xsup[s]; j <= last; ++j)
      for (int64_t p = b_indptr[j]; p < b_indptr[j + 1]; ++p) {
        int64_t i = b_indices[p];
        if (i > last && mark[i] != s) { mark[i] = s; rows.push_back(i); }
      }
    for (int64_t c : children[s])
      for (int64_t i : h->structs[c])
        if (i > last && mark[i] != s) { mark[i] = s; rows.push_back(i); }
    std::sort(rows.begin(), rows.end());
    h->structs[s] = rows;
  };

  for (auto& sups : bylevel) {
    int64_t cnt = (int64_t)sups.size();
    int64_t use = std::min(nt, cnt);
    if (use <= 1 || cnt < 64) {
      ensure_marks(1);
      std::vector<int64_t> rows;
      for (int64_t s : sups) do_sup(s, marks[0], rows);
    } else {
      ensure_marks(use);
      std::vector<std::thread> pool;
      pool.reserve((size_t)use);
      for (int64_t t = 0; t < use; ++t)
        pool.emplace_back([&, t]() {
          std::vector<int64_t> rows;
          for (int64_t i = t; i < cnt; i += use)
            do_sup(sups[i], marks[t], rows);
        });
      for (auto& th : pool) th.join();
    }
  }
  for (auto& v : h->structs) h->total += (int64_t)v.size();
  return h;
}

int64_t slu_symbfact_total(void* handle) {
  return static_cast<SymbHandle*>(handle)->total;
}

void slu_symbfact_sizes(void* handle, int64_t* sizes) {
  auto* h = static_cast<SymbHandle*>(handle);
  for (size_t s = 0; s < h->structs.size(); ++s)
    sizes[s] = (int64_t)h->structs[s].size();
}

void slu_symbfact_fill(void* handle, int64_t* flat) {
  auto* h = static_cast<SymbHandle*>(handle);
  int64_t off = 0;
  for (auto& vec : h->structs) {
    std::memcpy(flat + off, vec.data(), vec.size() * sizeof(int64_t));
    off += (int64_t)vec.size();
  }
}

void slu_symbfact_free(void* handle) {
  delete static_cast<SymbHandle*>(handle);
}

// ---------------------------------------------------- batch residual
// r_m = b_m - A_m x_m and the componentwise backward error
// max_i |r_i| / (|A||x| + |b|)_i of B systems on ONE CSR pattern, in
// one pass over the values (batch/engine.batch_solve's refinement:
// the residual of every member a pass, in the refine dtype, where a
// TPU's float64 is two float32 words and cannot hold the guarantee).
// `vals` is (B, nnz) with entry k of the pattern at src[k] (src null:
// at k); x, b, r are (B, n, nrhs).  A row sums in the pattern's order
// from zero, as scipy's csr_matvec does, so r is bitwise the
// block-diagonal scipy product's (models/refine.py keeps that twin).
// A member with a NaN anywhere reads berr NaN.  Members go to
// std::thread workers in chunks: they share nothing.
}  // extern "C"

template <typename T>
static void batch_residual_range(
    int64_t m0, int64_t m1, int64_t n, int64_t nrhs, int64_t nnz,
    const int64_t* indptr, const int64_t* indices, const int64_t* src,
    const T* vals, const T* x, const T* b, T* r, T* berr) {
  for (int64_t m = m0; m < m1; ++m) {
    const T* v = vals + m * nnz;
    const T* xm = x + m * n * nrhs;
    const T* bm = b + m * n * nrhs;
    T* rm = r + m * n * nrhs;
    T worst = 0;
    bool nan = false;
    for (int64_t i = 0; i < n; ++i) {
      for (int64_t c = 0; c < nrhs; ++c) {
        T s = 0, a = 0;
        for (int64_t k = indptr[i]; k < indptr[i + 1]; ++k) {
          const T av = v[src ? src[k] : k];
          const T xv = xm[indices[k] * nrhs + c];
          s += av * xv;
          a += std::fabs(av) * std::fabs(xv);
        }
        const T bi = bm[i * nrhs + c];
        const T ri = bi - s;
        rm[i * nrhs + c] = ri;
        T den = a + std::fabs(bi);
        if (den == 0) den = 1;
        const T q = std::fabs(ri) / den;
        if (q != q) nan = true;
        else if (q > worst) worst = q;
      }
    }
    berr[m] = nan ? std::numeric_limits<T>::quiet_NaN() : worst;
  }
}

template <typename T>
static void batch_residual(
    int64_t B, int64_t n, int64_t nrhs, int64_t nnz,
    const int64_t* indptr, const int64_t* indices, const int64_t* src,
    const T* vals, const T* x, const T* b, T* r, T* berr,
    int64_t threads) {
  if (threads <= 0) {
    unsigned hc = std::thread::hardware_concurrency();
    threads = std::min<int64_t>(hc ? hc : 1, 8);
  }
  // members are handed out in chunks from one counter, not split up
  // front: on a host whose cores are shared a thread that is held up
  // leaves its share to the others
  const int64_t chunk = 16;
  threads = std::max<int64_t>(
      1, std::min(threads, (B + chunk - 1) / chunk));
  std::atomic<int64_t> next{0};
  auto work = [&]() {
    for (;;) {
      const int64_t m0 = next.fetch_add(chunk);
      if (m0 >= B) break;
      batch_residual_range<T>(m0, std::min(B, m0 + chunk), n, nrhs, nnz,
                              indptr, indices, src, vals, x, b, r, berr);
    }
  };
  std::vector<std::thread> pool;
  for (int64_t t = 1; t < threads; ++t) pool.emplace_back(work);
  work();
  for (auto& th : pool) th.join();
}

extern "C" {

void slu_batch_residual_f64(
    int64_t B, int64_t n, int64_t nrhs, int64_t nnz,
    const int64_t* indptr, const int64_t* indices, const int64_t* src,
    const double* vals, const double* x, const double* b, double* r,
    double* berr, int64_t threads) {
  batch_residual<double>(B, n, nrhs, nnz, indptr, indices, src, vals, x,
                         b, r, berr, threads);
}

void slu_batch_residual_f32(
    int64_t B, int64_t n, int64_t nrhs, int64_t nnz,
    const int64_t* indptr, const int64_t* indices, const int64_t* src,
    const float* vals, const float* x, const float* b, float* r,
    float* berr, int64_t threads) {
  batch_residual<float>(B, n, nrhs, nnz, indptr, indices, src, vals, x,
                        b, r, berr, threads);
}

// ------------------------------------------------------------- cpuid
// Implementation shared with the tiny standalone helper
// (csrc/slu_cpuid.cc) — see csrc/slu_cpuid.h for the rationale.
int64_t slu_cpuid_words(int64_t* out, int64_t nwords) {
  return slu_cpuid_words_impl(out, nwords);
}

int64_t slu_version() { return 7; }

}  // extern "C"
