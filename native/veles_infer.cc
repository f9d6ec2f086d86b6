/* veles_tpu native inference runtime.
 *
 * Role parity with libVeles (reference: libVeles/src/
 * workflow_loader.cc:46-131 — archive extract → unit table → chain;
 * unit.cc / unit_factory.cc — per-type Execute implementations).
 * Parses the model.bin layout written by veles_tpu/export.py
 * (_pack_binary) and executes the forward chain in plain C++ —
 * NHWC activations, HWIO conv weights, semantics mirrored from
 * ExportedModel.forward_numpy (the Python reference used by the
 * parity tests).
 *
 * Build: `make -C native` → libveles_infer.so + veles_infer CLI.
 * Only system zlib is linked (no vendored deps — the reference
 * vendored libarchive/zlib/eina; standard libs suffice today).
 */
#include "veles_infer.h"

#include <zlib.h>

#include <algorithm>
#include <cmath>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <map>
#include <memory>
#include <string>
#include <vector>

namespace {

thread_local std::string g_error;

void set_error(const std::string &msg) { g_error = msg; }

/* ---- model.bin parsing ---------------------------------------------- */

struct Cursor {
  const uint8_t *p, *end;
  bool ok = true;
  template <typename T> T read() {
    T v{};
    if (p + sizeof(T) > end) { ok = false; return v; }
    std::memcpy(&v, p, sizeof(T));
    p += sizeof(T);
    return v;
  }
  std::string read_str() {
    uint16_t n = read<uint16_t>();
    if (!ok || p + n > end) { ok = false; return ""; }
    std::string s(reinterpret_cast<const char *>(p), n);
    p += n;
    return s;
  }
};

struct Param {
  std::vector<uint32_t> dims;
  std::vector<float> data;
};

struct UnitDesc {
  std::string type, name;
  std::map<std::string, double> cfg;
  std::map<std::string, Param> params;
  double cfgv(const std::string &key, double dflt = 0.0) const {
    auto it = cfg.find(key);
    return it == cfg.end() ? dflt : it->second;
  }
};

struct Shape {           /* activation shape per sample */
  int h = 1, w = 1, c = 1;
  bool spatial = false;  /* false → flat vector of size c */
  int size() const { return h * w * c; }
};

}  // namespace

struct VtModel {
  std::vector<UnitDesc> units;
  std::vector<Shape> shapes;  /* shapes[i] = input of unit i;
                                 back() = final output */
  int in_size = 0, out_size = 0;
};

namespace {

/* ---- activations (mirror of export.py _ACTS) ------------------------ */

constexpr float kTanhA = 1.7159f, kTanhB = 0.6666f;

inline float act_tanh(float v) { return kTanhA * std::tanh(kTanhB * v); }
inline float act_softplus(float v) {
  return std::log1p(std::exp(-std::fabs(v))) + std::max(v, 0.0f);
}
inline float act_str(float v) { return std::max(v, 0.0f); }
inline float act_sigmoid(float v) { return 1.0f / (1.0f + std::exp(-v)); }

enum class Act { kLinear, kTanh, kSoftplus, kStr, kSigmoid, kSoftmax };

Act act_of(const std::string &type) {
  if (type == "all2all_tanh" || type == "conv_tanh" ||
      type == "activation_tanh" || type == "all2all_deconv_tanh")
    return Act::kTanh;
  if (type == "all2all_relu" || type == "conv_relu" ||
      type == "activation_relu")
    return Act::kSoftplus;
  if (type == "all2all_str" || type == "conv_str" ||
      type == "activation_str")
    return Act::kStr;
  if (type == "all2all_sigmoid" || type == "conv_sigmoid" ||
      type == "activation_sigmoid" ||
      type == "all2all_deconv_sigmoid" || type == "rbm")
    return Act::kSigmoid;
  if (type == "softmax") return Act::kSoftmax;
  return Act::kLinear;
}

void apply_act(Act act, float *v, int n, int row_len) {
  switch (act) {
    case Act::kLinear: return;
    case Act::kTanh:
      for (int i = 0; i < n; ++i) v[i] = act_tanh(v[i]);
      return;
    case Act::kSoftplus:
      for (int i = 0; i < n; ++i) v[i] = act_softplus(v[i]);
      return;
    case Act::kStr:
      for (int i = 0; i < n; ++i) v[i] = act_str(v[i]);
      return;
    case Act::kSigmoid:
      for (int i = 0; i < n; ++i) v[i] = act_sigmoid(v[i]);
      return;
    case Act::kSoftmax:
      for (int r = 0; r < n / row_len; ++r) {
        float *row = v + r * row_len;
        float mx = row[0];
        for (int j = 1; j < row_len; ++j) mx = std::max(mx, row[j]);
        float sum = 0.0f;
        for (int j = 0; j < row_len; ++j) {
          row[j] = std::exp(row[j] - mx);
          sum += row[j];
        }
        for (int j = 0; j < row_len; ++j) row[j] /= sum;
      }
      return;
  }
}

/* ---- per-unit Execute (reference: unit.h:41) ------------------------ */

/* y[rows,out] = x[rows,in] @ w[in,out] + b — the one GEMM kernel
 * shared by dense units and the transformer block. */
void matmul_bias(const float *x, const float *w, const float *b,
                 float *y, int rows, int in, int out) {
  for (int r = 0; r < rows; ++r) {
    float *yr = y + (size_t)r * out;
    for (int j = 0; j < out; ++j) yr[j] = b ? b[j] : 0.0f;
    const float *xr = x + (size_t)r * in;
    for (int i = 0; i < in; ++i) {
      const float xi = xr[i];
      if (xi == 0.0f) continue;
      const float *wr = w + (size_t)i * out;
      for (int j = 0; j < out; ++j) yr[j] += xi * wr[j];
    }
  }
}

void run_dense(const UnitDesc &u, const float *in, float *out,
               int batch, int fan_in, int n_out) {
  const float *b = nullptr;
  auto bit = u.params.find("bias");
  if (bit != u.params.end()) b = bit->second.data.data();
  matmul_bias(in, u.params.at("weights").data.data(), b, out,
              batch, fan_in, n_out);
  apply_act(act_of(u.type), out, batch * n_out, n_out);
}

void run_conv(const UnitDesc &u, const float *in, float *out,
              int batch, const Shape &si, const Shape &so) {
  const Param &w = u.params.at("weights"); /* HWIO */
  const int ky = w.dims[0], kx = w.dims[1], ci = w.dims[2],
            co = w.dims[3];
  const float *b = nullptr;
  auto bit = u.params.find("bias");
  if (bit != u.params.end()) b = bit->second.data.data();
  const int pt = (int)u.cfgv("pad_top"), pl = (int)u.cfgv("pad_left");
  const int sh = (int)u.cfgv("stride_h", 1),
            sw = (int)u.cfgv("stride_w", 1);
  for (int s = 0; s < batch; ++s) {
    const float *x = in + s * si.size();
    float *y = out + s * so.size();
    for (int oy = 0; oy < so.h; ++oy)
      for (int ox = 0; ox < so.w; ++ox) {
        float *yp = y + (oy * so.w + ox) * co;
        for (int j = 0; j < co; ++j) yp[j] = b ? b[j] : 0.0f;
        const int iy0 = oy * sh - pt, ix0 = ox * sw - pl;
        for (int dy = 0; dy < ky; ++dy) {
          const int iy = iy0 + dy;
          if (iy < 0 || iy >= si.h) continue; /* zero padding */
          for (int dx = 0; dx < kx; ++dx) {
            const int ix = ix0 + dx;
            if (ix < 0 || ix >= si.w) continue;
            const float *xp = x + (iy * si.w + ix) * ci;
            const float *wp =
                w.data.data() + ((dy * kx + dx) * ci) * co;
            for (int i = 0; i < ci; ++i) {
              const float xi = xp[i];
              const float *wr = wp + i * co;
              for (int j = 0; j < co; ++j) yp[j] += xi * wr[j];
            }
          }
        }
      }
  }
  apply_act(act_of(u.type), out, batch * so.size(), co);
}

void run_pool(const UnitDesc &u, const float *in, float *out,
              int batch, const Shape &si, const Shape &so) {
  const int ky = (int)u.cfgv("ky"), kx = (int)u.cfgv("kx");
  const int pt = (int)u.cfgv("pad_top"), pl = (int)u.cfgv("pad_left");
  const int sh = (int)u.cfgv("stride_h", 1),
            sw = (int)u.cfgv("stride_w", 1);
  const bool is_avg = u.type == "avg_pooling";
  const bool is_abs = u.type == "maxabs_pooling";
  const int c = si.c;
  for (int s = 0; s < batch; ++s) {
    const float *x = in + s * si.size();
    float *y = out + s * so.size();
    for (int oy = 0; oy < so.h; ++oy)
      for (int ox = 0; ox < so.w; ++ox) {
        float *yp = y + (oy * so.w + ox) * c;
        const int iy0 = oy * sh - pt, ix0 = ox * sw - pl;
        for (int j = 0; j < c; ++j) {
          float best = 0.0f, sum = 0.0f;
          int count = 0;
          bool first = true;
          for (int dy = 0; dy < ky; ++dy) {
            const int iy = iy0 + dy;
            if (iy < 0 || iy >= si.h) continue;
            for (int dx = 0; dx < kx; ++dx) {
              const int ix = ix0 + dx;
              if (ix < 0 || ix >= si.w) continue;
              const float v = x[(iy * si.w + ix) * c + j];
              if (is_avg) {
                sum += v;
                ++count;
              } else if (first ||
                         (is_abs ? std::fabs(v) > std::fabs(best)
                                 : v > best)) {
                best = v;
                first = false;
              }
            }
          }
          /* A window lying entirely in padding: the Python parity
           * path (_pool_numpy) reduces an all-NaN slice → NaN for
           * the max variants, 0.0 for avg. Mirror that. */
          yp[j] = is_avg ? (count ? sum / count : 0.0f)
                         : (first ? std::nanf("") : best);
        }
      }
  }
}

void run_lrn(const UnitDesc &u, const float *in, float *out,
             int batch, const Shape &si) {
  const double alpha = u.cfgv("alpha"), beta = u.cfgv("beta"),
               k = u.cfgv("k");
  const int n = (int)u.cfgv("n"), c = si.c, half = n / 2;
  const int pixels = batch * si.h * si.w;
  for (int px = 0; px < pixels; ++px) {
    const float *x = in + px * c;
    float *y = out + px * c;
    for (int j = 0; j < c; ++j) {
      const int lo = std::max(0, j - half);
      const int hi = std::min(c, j + (n - 1 - half) + 1);
      double ssum = 0.0;
      for (int i = lo; i < hi; ++i) ssum += (double)x[i] * x[i];
      y[j] = (float)(x[j] /
                     std::pow(k + (alpha / n) * ssum, beta));
    }
  }
}

/* Kohonen forward: squared distance to every SOM neuron, weights
 * stored (n_neurons, n_in) row-major (KohonenForward.distances). */
void run_kohonen(const UnitDesc &u, const float *in, float *out,
                 int batch, int fan_in, int n_out) {
  const Param &w = u.params.at("weights");
  for (int s = 0; s < batch; ++s) {
    const float *x = in + s * fan_in;
    float *y = out + s * n_out;
    for (int j = 0; j < n_out; ++j) {
      const float *wr = w.data.data() + (size_t)j * fan_in;
      double d = 0.0;
      for (int i = 0; i < fan_in; ++i) {
        const double t = (double)x[i] - wr[i];
        d += t * t;
      }
      y[j] = (float)d;
    }
  }
}

/* ---- transformer family (no reference counterpart; mirrors
 * ExportedModel._transformer_numpy / znicz/attention.py) ----------- */

void run_embedding(const UnitDesc &u, const float *in, float *out,
                   int batch, int seq, int embed) {
  const Param &w = u.params.at("weights");
  const Param &pos = u.params.at("pos");
  const int vocab = (int)w.dims[0];
  for (int s = 0; s < batch; ++s)
    for (int t = 0; t < seq; ++t) {
      int tok = (int)in[s * seq + t];
      if (tok < 0) tok = 0;
      if (tok >= vocab) tok = vocab - 1;
      const float *we = w.data.data() + (size_t)tok * embed;
      const float *pe = pos.data.data() + (size_t)t * embed;
      float *y = out + ((size_t)s * seq + t) * embed;
      for (int e = 0; e < embed; ++e) y[e] = we[e] + pe[e];
    }
}

void layer_norm(const float *x, const float *g, const float *b,
                float *y, int n, float eps = 1e-5f) {
  double mu = 0.0;
  for (int i = 0; i < n; ++i) mu += x[i];
  mu /= n;
  double var = 0.0;
  for (int i = 0; i < n; ++i) var += (x[i] - mu) * (x[i] - mu);
  var /= n;
  const float r = 1.0f / std::sqrt((float)var + eps);
  for (int i = 0; i < n; ++i)
    y[i] = ((float)(x[i] - mu)) * r * g[i] + b[i];
}

/* Per-unit-call scratch for transformer_attention: allocated ONCE
 * by the caller and reused across the batch loop (the attention is
 * the native serving hot path). */
struct AttnScratch {
  std::vector<float> h, q, k, v, attn, scores, qkv;
  AttnScratch(int seq, int embed)
      : h((size_t)seq * embed), q(h.size()), k(h.size()),
        v(h.size()), attn(h.size()), scores((size_t)seq) {}
  /* qkv (seq × 3·embed) is only needed for fused-wqkv artifacts —
   * sized on first fused use so unfused models never pay the 3×
   * allocation. */
  float *qkv_buf(size_t n) {
    if (qkv.size() < n) qkv.resize(n);
    return qkv.data();
  }
};

/* One sample's pre-LN attention with residual:
 * res = x + attn(LN1(x)) @ wo + bo. */
void transformer_attention(const UnitDesc &u, const float *x,
                           float *res, int seq, int embed,
                           AttnScratch &ws) {
  const int H = (int)u.cfgv("n_heads", 1);
  const bool causal = u.cfgv("causal", 1.0) != 0.0;
  const int D = embed / H;
  const float scale = 1.0f / std::sqrt((float)D);
  auto P = [&](const char *n) {
    return u.params.at(n).data.data();
  };
  std::vector<float> &h = ws.h, &q = ws.q, &k = ws.k, &v = ws.v,
      &attn = ws.attn, &scores = ws.scores;
  for (int t = 0; t < seq; ++t)
    layer_norm(x + (size_t)t * embed, P("ln1_g"), P("ln1_b"),
               h.data() + (size_t)t * embed, embed);
  if (u.params.count("wqkv")) {
    /* Fused-QKV artifact (znicz/attention.fuse_qkv_arrays): one
     * (E, 3E) matmul whose columns are head-major [q_h|k_h|v_h]
     * blocks of D each; de-interleave into the per-head q/k/v
     * buffers the attention loop below expects. */
    float *qkvb = ws.qkv_buf((size_t)seq * 3 * embed);
    matmul_bias(h.data(), P("wqkv"), P("bqkv"), qkvb, seq,
                embed, 3 * embed);
    float *dst[3] = {q.data(), k.data(), v.data()};
    for (int t = 0; t < seq; ++t)
      for (int head = 0; head < H; ++head)
        for (int part = 0; part < 3; ++part) {
          const float *src = qkvb +
              (size_t)t * 3 * embed +
              ((size_t)head * 3 + part) * D;
          float *d = dst[part] + (size_t)t * embed +
              (size_t)head * D;
          for (int e = 0; e < D; ++e) d[e] = src[e];
        }
  } else {
    matmul_bias(h.data(), P("wq"), P("bq"), q.data(), seq, embed,
                embed);
    matmul_bias(h.data(), P("wk"), P("bk"), k.data(), seq, embed,
                embed);
    matmul_bias(h.data(), P("wv"), P("bv"), v.data(), seq, embed,
                embed);
  }
  std::fill(attn.begin(), attn.end(), 0.0f);
  for (int head = 0; head < H; ++head) {
    const int off = head * D;
    for (int i = 0; i < seq; ++i) {
      const int lim = causal ? i + 1 : seq;
      float mx = -1e30f;
      for (int j = 0; j < lim; ++j) {
        double dot = 0.0;
        const float *qi = q.data() + (size_t)i * embed + off;
        const float *kj = k.data() + (size_t)j * embed + off;
        for (int d = 0; d < D; ++d) dot += (double)qi[d] * kj[d];
        scores[j] = (float)dot * scale;
        mx = std::max(mx, scores[j]);
      }
      double sum = 0.0;
      for (int j = 0; j < lim; ++j) {
        scores[j] = std::exp(scores[j] - mx);
        sum += scores[j];
      }
      float *ai = attn.data() + (size_t)i * embed + off;
      for (int j = 0; j < lim; ++j) {
        const float p = (float)(scores[j] / sum);
        const float *vj = v.data() + (size_t)j * embed + off;
        for (int d = 0; d < D; ++d) ai[d] += p * vj[d];
      }
    }
  }
  /* res = x + attn @ wo + bo */
  matmul_bias(attn.data(), P("wo"), P("bo"), res, seq, embed,
              embed);
  for (size_t i = 0; i < (size_t)seq * embed; ++i)
    res[i] += x[i];
}

void run_transformer_block(const UnitDesc &u, const float *in,
                           float *out, int batch, int seq,
                           int embed) {
  auto P = [&](const char *n) {
    return u.params.at(n).data.data();
  };
  const int hidden = (int)u.params.at("w1").dims[1];
  std::vector<float> res((size_t)seq * embed),
      ln2((size_t)seq * embed), mlp((size_t)seq * hidden);
  AttnScratch ws(seq, embed);
  for (int s = 0; s < batch; ++s) {
    const float *x = in + (size_t)s * seq * embed;
    float *y = out + (size_t)s * seq * embed;
    transformer_attention(u, x, res.data(), seq, embed, ws);
    for (int t = 0; t < seq; ++t)
      layer_norm(res.data() + (size_t)t * embed, P("ln2_g"),
                 P("ln2_b"), ln2.data() + (size_t)t * embed, embed);
    matmul_bias(ln2.data(), P("w1"), P("b1"), mlp.data(), seq,
                embed, hidden);
    for (float &m : mlp) m = std::max(m, 0.0f);
    matmul_bias(mlp.data(), P("w2"), P("b2"), y, seq, hidden,
                embed);
    for (size_t i = 0; i < (size_t)seq * embed; ++i)
      y[i] += res[i];
  }
}

void run_mean_disp(const UnitDesc &u, const float *in, float *out,
                   int batch, int sample) {
  const float *mean = u.params.at("mean").data.data();
  const float *rdisp = u.params.at("rdisp").data.data();
  for (int s = 0; s < batch; ++s)
    for (int i = 0; i < sample; ++i)
      out[s * sample + i] = (in[s * sample + i] - mean[i]) * rdisp[i];
}

/* ---- shape propagation (mirror of export geometry) ------------------ */

/* Looks up a required param and checks its element count against the
 * config-derived geometry. The executors index param arrays by that
 * geometry, so a model.bin whose dims are self-consistent with its
 * data but inconsistent with the config must be rejected here, not
 * read out of bounds later. */
const Param *checked_param(const UnitDesc &u, const char *pname,
                           size_t want) {
  auto it = u.params.find(pname);
  if (it == u.params.end()) {
    set_error("unit " + u.name + ": missing param " + pname);
    return nullptr;
  }
  if (it->second.data.size() != want) {
    set_error("unit " + u.name + ": param " + pname + " has " +
              std::to_string(it->second.data.size()) +
              " elements, geometry wants " + std::to_string(want));
    return nullptr;
  }
  return &it->second;
}

bool check_optional_bias(const UnitDesc &u, size_t want) {
  auto it = u.params.find("bias");
  if (it != u.params.end() && it->second.data.size() != want) {
    set_error("unit " + u.name + ": bias size mismatch");
    return false;
  }
  return true;
}

/* The attention projection comes in two layouts: the classic three
 * (E, E) wq/wk/wv matrices, or the fused head-major (E, 3E) wqkv
 * (znicz/attention.fuse_qkv_arrays) — the executor dispatches on
 * wqkv's presence, so validation must too. */
bool check_attention_proj(const UnitDesc &u, size_t E) {
  if (u.params.count("wqkv"))
    return checked_param(u, "wqkv", E * 3 * E) &&
           checked_param(u, "bqkv", 3 * E);
  const char *vecs[] = {"bq", "bk", "bv"};
  for (const char *n : vecs)
    if (!checked_param(u, n, E)) return false;
  const char *mats[] = {"wq", "wk", "wv"};
  for (const char *n : mats)
    if (!checked_param(u, n, E * E)) return false;
  return true;
}

bool infer_shapes(VtModel *m) {
  for (size_t i = 0; i < m->units.size(); ++i) {
    const UnitDesc &u = m->units[i];
    const Shape &si = m->shapes[i];
    Shape so = si;
    const std::string &t = u.type;
    if (t.rfind("all2all", 0) == 0 || t == "softmax" ||
        t == "rbm") {
      const int n_out = (int)u.cfgv("n_out");
      if (n_out <= 0) {
        set_error("unit " + u.name + ": bad n_out");
        return false;
      }
      if (!checked_param(u, "weights", (size_t)si.size() * n_out) ||
          !check_optional_bias(u, (size_t)n_out))
        return false;
      so = Shape{1, 1, n_out, false};
    } else if (t == "kohonen") {
      const int n_out = (int)u.cfgv("n_out");
      if (n_out <= 0) {
        set_error("unit " + u.name + ": bad n_out");
        return false;
      }
      /* run_kohonen walks rows of length si.size(): dims must agree
       * with the propagated activation, not just the element count. */
      auto wit = u.params.find("weights");
      if (!checked_param(u, "weights", (size_t)si.size() * n_out) ||
          wit->second.dims.size() != 2 ||
          (int)wit->second.dims[0] != n_out ||
          (int)wit->second.dims[1] != si.size()) {
        set_error("unit " + u.name + ": kohonen weights must be "
                  "(n_neurons, n_in)");
        return false;
      }
      so = Shape{1, 1, n_out, false};
    } else if (t.rfind("conv", 0) == 0) {
      auto wit = u.params.find("weights");
      if (wit == u.params.end() || wit->second.dims.size() != 4) {
        set_error("unit " + u.name + ": conv weights must be HWIO");
        return false;
      }
      const Param &w = wit->second;
      const int ky = w.dims[0], kx = w.dims[1], ci = w.dims[2],
                co = w.dims[3];
      if (ky <= 0 || kx <= 0 || ci <= 0 || co <= 0) {
        set_error("unit " + u.name + ": bad conv kernel dims");
        return false;
      }
      /* run_conv walks the input with ci = w.dims[2]; it must match
       * the propagated channel count or reads go out of bounds. */
      if (ci != si.c) {
        set_error("unit " + u.name + ": conv expects " +
                  std::to_string(ci) + " input channels, activation "
                  "has " + std::to_string(si.c));
        return false;
      }
      if (!check_optional_bias(u, (size_t)co)) return false;
      const int sh = (int)u.cfgv("stride_h", 1),
                sw = (int)u.cfgv("stride_w", 1);
      if (sh <= 0 || sw <= 0) {
        set_error("unit " + u.name + ": bad conv stride");
        return false;
      }
      const int ph = (int)(u.cfgv("pad_top") + u.cfgv("pad_bottom"));
      const int pw = (int)(u.cfgv("pad_left") + u.cfgv("pad_right"));
      so.h = (si.h + ph - ky) / sh + 1;
      so.w = (si.w + pw - kx) / sw + 1;
      so.c = co;
      so.spatial = true;
      if (so.h <= 0 || so.w <= 0) {
        set_error("unit " + u.name + ": conv output collapses");
        return false;
      }
    } else if (t.find("pooling") != std::string::npos) {
      const int ky = (int)u.cfgv("ky"), kx = (int)u.cfgv("kx");
      const int sh = (int)u.cfgv("stride_h", 1),
                sw = (int)u.cfgv("stride_w", 1);
      if (ky <= 0 || kx <= 0 || sh <= 0 || sw <= 0) {
        set_error("unit " + u.name + ": bad pooling geometry");
        return false;
      }
      const int ph = (int)(u.cfgv("pad_top") + u.cfgv("pad_bottom"));
      const int pw = (int)(u.cfgv("pad_left") + u.cfgv("pad_right"));
      /* ceil mode (znicz pools the ragged tail) */
      so.h = (si.h + ph - ky + sh - 1) / sh + 1;
      so.w = (si.w + pw - kx + sw - 1) / sw + 1;
      if (so.h <= 0 || so.w <= 0) {
        set_error("unit " + u.name + ": pooling output collapses");
        return false;
      }
    } else if (t == "norm") {
      if ((int)u.cfgv("n") <= 0) {
        set_error("unit " + u.name + ": bad LRN window");
        return false;
      }
    } else if (t == "embedding") {
      const int seq = si.size();
      const int embed = (int)u.cfgv("embed_dim");
      const int vocab = (int)u.cfgv("vocab_size");
      if (seq <= 0 || embed <= 0 || vocab <= 0) {
        set_error("unit " + u.name + ": bad embedding geometry");
        return false;
      }
      if (!checked_param(u, "weights", (size_t)vocab * embed))
        return false;
      auto pit = u.params.find("pos");
      if (pit == u.params.end() || pit->second.dims.size() != 2 ||
          (int)pit->second.dims[0] < seq ||
          (int)pit->second.dims[1] != embed) {
        set_error("unit " + u.name + ": positional table must be "
                  "(>=seq, embed)");
        return false;
      }
      so = Shape{seq, 1, embed, true};
    } else if (t == "transformer_block") {
      const int seq = si.h, embed = si.c;
      const int heads = (int)u.cfgv("n_heads", 1);
      if (si.w != 1 || seq <= 0 || embed <= 0 || heads <= 0 ||
          embed % heads) {
        set_error("unit " + u.name + ": bad transformer geometry");
        return false;
      }
      auto w1it = u.params.find("w1");
      if (w1it == u.params.end() || w1it->second.dims.size() != 2 ||
          (int)w1it->second.dims[0] != embed) {
        set_error("unit " + u.name + ": w1 must be (embed, hidden)");
        return false;
      }
      const int hidden = (int)w1it->second.dims[1];
      const size_t E = (size_t)embed;
      const char *vecs_e[] = {"ln1_g", "ln1_b", "bo", "ln2_g",
                              "ln2_b", "b2"};
      for (const char *n : vecs_e)
        if (!checked_param(u, n, E)) return false;
      if (!check_attention_proj(u, E) ||
          !checked_param(u, "wo", E * E))
        return false;
      if (!checked_param(u, "b1", (size_t)hidden) ||
          !checked_param(u, "w2", (size_t)hidden * embed))
        return false;
      /* shape-preserving */
    } else if (t == "lm_head") {
      const int n_out = (int)u.cfgv("n_out");
      if (si.w != 1 || n_out <= 0) {
        set_error("unit " + u.name + ": bad lm_head geometry");
        return false;
      }
      if (!checked_param(u, "weights", (size_t)si.c * n_out) ||
          !check_optional_bias(u, (size_t)n_out))
        return false;
      so = Shape{si.h, 1, n_out, true};
    } else if (t == "mean_disp") {
      if (!checked_param(u, "mean", (size_t)si.size()) ||
          !checked_param(u, "rdisp", (size_t)si.size()))
        return false;
    } else if (t == "dropout" || t.rfind("activation_", 0) == 0) {
      /* shape-preserving, no params */
    } else {
      set_error("unknown unit type: " + t);
      return false;
    }
    m->shapes.push_back(so);
  }
  m->in_size = m->shapes.front().size();
  m->out_size = m->shapes.back().size();
  return true;
}

bool parse_model(const uint8_t *data, size_t size, VtModel *m) {
  Cursor c{data, data + size};
  char magic[4];
  for (char &ch : magic) ch = (char)c.read<uint8_t>();
  if (!c.ok || std::memcmp(magic, "VTPM", 4) != 0) {
    set_error("bad magic (not a veles-tpu model.bin)");
    return false;
  }
  const uint32_t version = c.read<uint32_t>();
  if (version > 1) {
    set_error("model.bin version too new: " + std::to_string(version));
    return false;
  }
  const uint32_t n_units = c.read<uint32_t>();
  const uint32_t in_ndim = c.read<uint32_t>();
  if (!c.ok || in_ndim == 0 || in_ndim > 8) {
    set_error("bad input ndim");
    return false;
  }
  std::vector<uint32_t> in_shape(in_ndim);
  uint64_t in_count = 1;
  for (auto &d : in_shape) {
    d = c.read<uint32_t>();
    /* Same discipline as params: hostile dims must fail here, not
     * overflow Shape::size() into a small/negative int that defeats
     * every downstream geometry check. */
    if (!c.ok || d == 0 || in_count > (uint64_t)INT32_MAX / d) {
      set_error("bad input shape");
      return false;
    }
    in_count *= d;
  }
  Shape s0;
  if (in_ndim == 3) {
    s0 = Shape{(int)in_shape[0], (int)in_shape[1], (int)in_shape[2],
               true};
  } else {
    s0 = Shape{1, 1, (int)in_count, false};
  }
  m->shapes.push_back(s0);
  for (uint32_t i = 0; i < n_units && c.ok; ++i) {
    UnitDesc u;
    u.type = c.read_str();
    u.name = c.read_str();
    const uint32_t n_cfg = c.read<uint32_t>();
    for (uint32_t j = 0; j < n_cfg && c.ok; ++j) {
      std::string key = c.read_str();
      u.cfg[key] = c.read<double>();
    }
    const uint32_t n_par = c.read<uint32_t>();
    for (uint32_t j = 0; j < n_par && c.ok; ++j) {
      std::string pname = c.read_str();
      Param p;
      const uint32_t ndim = c.read<uint32_t>();
      if (ndim > 8) {
        set_error("param ndim too large");
        return false;
      }
      uint64_t count = 1;
      for (uint32_t d = 0; d < ndim && c.ok; ++d) {
        const uint32_t dim = c.read<uint32_t>();
        p.dims.push_back(dim);
        /* Checked multiply: huge dims must fail, not wrap the
         * product below the truncation bound. */
        if (dim != 0 && count > UINT64_MAX / dim) {
          set_error("param dims overflow");
          return false;
        }
        count *= dim;
      }
      /* Overflow-safe bound: compare against remaining bytes, never
       * via pointer arithmetic that huge dims could wrap. */
      if (!c.ok ||
          count > (uint64_t)(c.end - c.p) / 4) {
        set_error("truncated param data");
        return false;
      }
      p.data.resize(count);
      std::memcpy(p.data.data(), c.p, count * 4);
      c.p += count * 4;
      u.params.emplace(std::move(pname), std::move(p));
    }
    m->units.push_back(std::move(u));
  }
  if (!c.ok) {
    set_error("truncated model.bin");
    return false;
  }
  return infer_shapes(m);
}

/* ---- container handling: raw model.bin OR .tgz ---------------------- */

bool read_file_inflated(const char *path, std::vector<uint8_t> *out) {
  /* gzread passes plain files through untouched, so one code path
   * serves both model.bin and model.veles.tgz. */
  gzFile f = gzopen(path, "rb");
  if (!f) {
    set_error(std::string("cannot open ") + path);
    return false;
  }
  uint8_t buf[1 << 16];
  int n;
  while ((n = gzread(f, buf, sizeof(buf))) > 0)
    out->insert(out->end(), buf, buf + n);
  gzclose(f);
  if (n < 0) {
    set_error("decompression failed");
    return false;
  }
  return true;
}

/* Minimal ustar walk: 512-byte headers, name at 0, octal size at
 * 124. */
bool find_in_tar(const std::vector<uint8_t> &tar,
                 const std::string &want, const uint8_t **blob,
                 size_t *blob_size) {
  size_t off = 0;
  while (off + 512 <= tar.size()) {
    const char *hdr = reinterpret_cast<const char *>(&tar[off]);
    if (hdr[0] == '\0') break; /* end blocks */
    std::string name(hdr, strnlen(hdr, 100));
    char size_field[13] = {0};
    std::memcpy(size_field, hdr + 124, 12);
    const size_t fsize = std::strtoul(size_field, nullptr, 8);
    if (name == want) {
      if (off + 512 + fsize > tar.size()) {
        set_error("truncated tar entry");
        return false;
      }
      *blob = &tar[off + 512];
      *blob_size = fsize;
      return true;
    }
    off += 512 + ((fsize + 511) / 512) * 512;
  }
  set_error("model.bin not found in archive");
  return false;
}

}  // namespace

/* ---- C API ----------------------------------------------------------- */

extern "C" {

VtModel *vt_load(const char *path) {
  /* No C++ exception may cross the C boundary: a corrupt file that
   * slips a huge allocation past parsing must surface as NULL +
   * vt_error, not std::terminate in the host process. */
  try {
    std::vector<uint8_t> raw;
    if (!read_file_inflated(path, &raw)) return nullptr;
    const uint8_t *blob = raw.data();
    size_t blob_size = raw.size();
    if (raw.size() < 4 || std::memcmp(raw.data(), "VTPM", 4) != 0) {
      if (!find_in_tar(raw, "model.bin", &blob, &blob_size))
        return nullptr;
    }
    auto model = std::make_unique<VtModel>();
    if (!parse_model(blob, blob_size, model.get())) return nullptr;
    return model.release();
  } catch (const std::exception &e) {
    set_error(std::string("load failed: ") + e.what());
    return nullptr;
  }
}

int vt_input_size(const VtModel *m) { return m ? m->in_size : -1; }
int vt_output_size(const VtModel *m) { return m ? m->out_size : -1; }
int vt_unit_count(const VtModel *m) {
  return m ? (int)m->units.size() : -1;
}
const char *vt_unit_type(const VtModel *m, int index) {
  if (!m || index < 0 || index >= (int)m->units.size()) return nullptr;
  return m->units[index].type.c_str();
}

int vt_forward(const VtModel *m, const float *input, int batch,
               float *output) try {
  if (!m || !input || !output || batch <= 0) {
    set_error("bad arguments");
    return 1;
  }
  std::vector<float> a(input, input + (size_t)batch * m->in_size);
  std::vector<float> b;
  for (size_t i = 0; i < m->units.size(); ++i) {
    const UnitDesc &u = m->units[i];
    const Shape &si = m->shapes[i];
    const Shape &so = m->shapes[i + 1];
    b.assign((size_t)batch * so.size(), 0.0f);
    const std::string &t = u.type;
    if (t.rfind("all2all", 0) == 0 || t == "softmax" ||
        t == "rbm") {
      run_dense(u, a.data(), b.data(), batch, si.size(), so.size());
    } else if (t == "kohonen") {
      run_kohonen(u, a.data(), b.data(), batch, si.size(),
                  so.size());
    } else if (t == "embedding") {
      run_embedding(u, a.data(), b.data(), batch, si.size(), so.c);
    } else if (t == "transformer_block") {
      run_transformer_block(u, a.data(), b.data(), batch, si.h,
                            si.c);
    } else if (t == "lm_head") {
      /* per-position dense: rows = batch × seq */
      run_dense(u, a.data(), b.data(), batch * si.h, si.c, so.c);
    } else if (t.rfind("conv", 0) == 0) {
      run_conv(u, a.data(), b.data(), batch, si, so);
    } else if (t.find("pooling") != std::string::npos) {
      run_pool(u, a.data(), b.data(), batch, si, so);
    } else if (t == "norm") {
      run_lrn(u, a.data(), b.data(), batch, si);
    } else if (t == "mean_disp") {
      run_mean_disp(u, a.data(), b.data(), batch, si.size());
    } else if (t == "dropout") {
      b = a;
    } else if (t.rfind("activation_", 0) == 0) {
      b = a;
      Act act = act_of(t);
      apply_act(act, b.data(), batch * so.size(), so.c);
    } else {
      set_error("unknown unit type at run time: " + t);
      return 1;
    }
    a.swap(b);
  }
  std::memcpy(output, a.data(),
              (size_t)batch * m->out_size * sizeof(float));
  return 0;
} catch (const std::exception &e) {
  set_error(std::string("forward failed: ") + e.what());
  return 1;
}

void vt_free(VtModel *m) { delete m; }

const char *vt_error(void) { return g_error.c_str(); }

}  /* extern "C" */

/* ---- CLI (role of the libVeles sample runner) ------------------------ */
#ifdef VELES_INFER_MAIN
int main(int argc, char **argv) {
  if (argc < 2) {
    std::fprintf(stderr,
                 "usage: %s <model.veles.tgz|model.bin> "
                 "[input.f32 [batch]]\n"
                 "Reads float32 samples from input.f32 (or zeros), "
                 "writes outputs as text to stdout.\n",
                 argv[0]);
    return 2;
  }
  VtModel *m = vt_load(argv[1]);
  if (!m) {
    std::fprintf(stderr, "load failed: %s\n", vt_error());
    return 1;
  }
  std::fprintf(stderr, "loaded: %d units, input %d, output %d\n",
               vt_unit_count(m), vt_input_size(m), vt_output_size(m));
  int batch = argc > 3 ? std::atoi(argv[3]) : 1;
  std::vector<float> in((size_t)batch * vt_input_size(m), 0.0f);
  if (argc > 2) {
    std::FILE *f = std::fopen(argv[2], "rb");
    if (!f) {
      std::fprintf(stderr, "cannot open %s\n", argv[2]);
      return 1;
    }
    size_t got = std::fread(in.data(), sizeof(float), in.size(), f);
    std::fclose(f);
    if (got != in.size()) {
      std::fprintf(stderr, "short read: %zu/%zu floats\n", got,
                   in.size());
      return 1;
    }
  }
  std::vector<float> out((size_t)batch * vt_output_size(m));
  if (vt_forward(m, in.data(), batch, out.data()) != 0) {
    std::fprintf(stderr, "forward failed: %s\n", vt_error());
    return 1;
  }
  for (int s = 0; s < batch; ++s) {
    for (int j = 0; j < vt_output_size(m); ++j)
      std::printf("%s%g", j ? " " : "", out[s * vt_output_size(m) + j]);
    std::printf("\n");
  }
  vt_free(m);
  return 0;
}
#endif
