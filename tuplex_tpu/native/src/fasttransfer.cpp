// Fast python<->columnar transfer kernels (CPython C API).
//
// The native analog of the reference's PythonContext fast paths
// (reference: tuplex/python/src/PythonContext.cc:823-919 —
// fastI64Parallelize / fastMixedSimpleTypeTupleTransfer / strDictParallelize:
// typed bulk conversion of python lists into partition buffers, with
// non-conforming elements routed to fallback). Here each column of a
// parallelize()/join-output batch is encoded by one C loop instead of a
// per-row python loop; buffers are returned as python `bytes` that numpy
// wraps zero-copy via np.frombuffer.
//
// Exposed module: _tuplex_native
//   encode_i64(list)  -> (data_bytes,  valid_bytes, bad_index_list)
//   encode_f64(list)  -> (data_bytes,  valid_bytes, bad_index_list)
//   encode_bool(list) -> (data_bytes,  valid_bytes, bad_index_list)
//   encode_str(list)  -> (mat_bytes, lens_bytes, valid_bytes, width,
//                         bad_index_list)
//   decode_str(mat_bytes, lens_bytes, width, n) -> list[str]
//
// "bad" = element whose type doesn't conform (including bool where int is
// expected — python bool is an int subtype but the type lattice separates
// them); None is VALID (valid=0) since Option columns carry a validity mask.

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <cstdint>
#include <cstring>
#include <vector>

namespace {

struct EncodedCommon {
  PyObject *valid_bytes = nullptr;
  PyObject *bad_list = nullptr;
};

static bool alloc_common(Py_ssize_t n, EncodedCommon &out) {
  out.valid_bytes = PyBytes_FromStringAndSize(nullptr, n);
  out.bad_list = PyList_New(0);
  return out.valid_bytes && out.bad_list;
}

static PyObject *encode_i64(PyObject *, PyObject *arg) {
  if (!PyList_Check(arg)) {
    PyErr_SetString(PyExc_TypeError, "expected list");
    return nullptr;
  }
  Py_ssize_t n = PyList_GET_SIZE(arg);
  PyObject *data = PyBytes_FromStringAndSize(nullptr, n * 8);
  EncodedCommon c;
  if (!data || !alloc_common(n, c)) return nullptr;
  int64_t *d = reinterpret_cast<int64_t *>(PyBytes_AS_STRING(data));
  char *v = PyBytes_AS_STRING(c.valid_bytes);
  for (Py_ssize_t i = 0; i < n; i++) {
    PyObject *o = PyList_GET_ITEM(arg, i);
    if (o == Py_None) {
      d[i] = 0;
      v[i] = 0;
      continue;
    }
    if (PyLong_Check(o) && !PyBool_Check(o)) {
      int overflow = 0;
      long long val = PyLong_AsLongLongAndOverflow(o, &overflow);
      if (!overflow) {
        d[i] = static_cast<int64_t>(val);
        v[i] = 1;
        continue;
      }
    }
    d[i] = 0;
    v[i] = 1;  // slot unusable; caller boxes the row
    PyObject *idx = PyLong_FromSsize_t(i);
    PyList_Append(c.bad_list, idx);
    Py_DECREF(idx);
  }
  return Py_BuildValue("(NNN)", data, c.valid_bytes, c.bad_list);
}

static PyObject *encode_f64(PyObject *, PyObject *arg) {
  if (!PyList_Check(arg)) {
    PyErr_SetString(PyExc_TypeError, "expected list");
    return nullptr;
  }
  Py_ssize_t n = PyList_GET_SIZE(arg);
  PyObject *data = PyBytes_FromStringAndSize(nullptr, n * 8);
  EncodedCommon c;
  if (!data || !alloc_common(n, c)) return nullptr;
  double *d = reinterpret_cast<double *>(PyBytes_AS_STRING(data));
  char *v = PyBytes_AS_STRING(c.valid_bytes);
  for (Py_ssize_t i = 0; i < n; i++) {
    PyObject *o = PyList_GET_ITEM(arg, i);
    if (o == Py_None) {
      d[i] = 0.0;
      v[i] = 0;
      continue;
    }
    if (PyFloat_Check(o)) {
      d[i] = PyFloat_AS_DOUBLE(o);
      v[i] = 1;
      continue;
    }
    d[i] = 0.0;
    v[i] = 1;
    PyObject *idx = PyLong_FromSsize_t(i);
    PyList_Append(c.bad_list, idx);
    Py_DECREF(idx);
  }
  return Py_BuildValue("(NNN)", data, c.valid_bytes, c.bad_list);
}

static PyObject *encode_bool(PyObject *, PyObject *arg) {
  if (!PyList_Check(arg)) {
    PyErr_SetString(PyExc_TypeError, "expected list");
    return nullptr;
  }
  Py_ssize_t n = PyList_GET_SIZE(arg);
  PyObject *data = PyBytes_FromStringAndSize(nullptr, n);
  EncodedCommon c;
  if (!data || !alloc_common(n, c)) return nullptr;
  char *d = PyBytes_AS_STRING(data);
  char *v = PyBytes_AS_STRING(c.valid_bytes);
  for (Py_ssize_t i = 0; i < n; i++) {
    PyObject *o = PyList_GET_ITEM(arg, i);
    if (o == Py_None) {
      d[i] = 0;
      v[i] = 0;
      continue;
    }
    if (PyBool_Check(o)) {
      d[i] = (o == Py_True) ? 1 : 0;
      v[i] = 1;
      continue;
    }
    d[i] = 0;
    v[i] = 1;
    PyObject *idx = PyLong_FromSsize_t(i);
    PyList_Append(c.bad_list, idx);
    Py_DECREF(idx);
  }
  return Py_BuildValue("(NNN)", data, c.valid_bytes, c.bad_list);
}

static PyObject *encode_str(PyObject *, PyObject *arg) {
  if (!PyList_Check(arg)) {
    PyErr_SetString(PyExc_TypeError, "expected list");
    return nullptr;
  }
  Py_ssize_t n = PyList_GET_SIZE(arg);
  // pass 1: utf8 views + max width
  std::vector<const char *> ptrs(static_cast<size_t>(n), nullptr);
  std::vector<Py_ssize_t> lens(static_cast<size_t>(n), 0);
  std::vector<Py_ssize_t> bad;
  Py_ssize_t w = 1;
  for (Py_ssize_t i = 0; i < n; i++) {
    PyObject *o = PyList_GET_ITEM(arg, i);
    if (o == Py_None) continue;
    if (PyUnicode_Check(o)) {
      Py_ssize_t sz = 0;
      const char *u = PyUnicode_AsUTF8AndSize(o, &sz);
      if (u) {
        ptrs[static_cast<size_t>(i)] = u;
        lens[static_cast<size_t>(i)] = sz;
        if (sz > w) w = sz;
        continue;
      }
      PyErr_Clear();
    }
    bad.push_back(i);
  }
  PyObject *mat = PyBytes_FromStringAndSize(nullptr, n * w);
  PyObject *lens_b = PyBytes_FromStringAndSize(nullptr, n * 4);
  PyObject *valid_b = PyBytes_FromStringAndSize(nullptr, n);
  PyObject *bad_list = PyList_New(0);
  if (!mat || !lens_b || !valid_b || !bad_list) return nullptr;
  char *m = PyBytes_AS_STRING(mat);
  int32_t *lp = reinterpret_cast<int32_t *>(PyBytes_AS_STRING(lens_b));
  char *v = PyBytes_AS_STRING(valid_b);
  memset(m, 0, static_cast<size_t>(n * w));
  for (Py_ssize_t i = 0; i < n; i++) {
    const char *u = ptrs[static_cast<size_t>(i)];
    if (u) {
      memcpy(m + i * w, u, static_cast<size_t>(lens[static_cast<size_t>(i)]));
      lp[i] = static_cast<int32_t>(lens[static_cast<size_t>(i)]);
      v[i] = 1;
    } else {
      lp[i] = 0;
      v[i] = 0;
    }
  }
  for (Py_ssize_t i : bad) {
    v[i] = 1;  // not a None: row must be boxed by the caller
    PyObject *idx = PyLong_FromSsize_t(i);
    PyList_Append(bad_list, idx);
    Py_DECREF(idx);
  }
  return Py_BuildValue("(NNNnN)", mat, lens_b, valid_b, w, bad_list);
}

// Arrow large_string buffers -> zero-padded [n, w] byte matrix + clamped
// int32 lens + unclamped int64 lens. The hot half of CSV/ORC ingestion
// (python fallback: runtime/columns.py arrow_string_to_leaf's fancy-index
// gather builds an [n, w] index matrix first — this is one pass of memcpy).
// `width` > 0 is the matrix's width, decided by the caller before any slice
// was built (a CSV source's planned width); 0 derives it from the slice,
// w = min(widest cell, maxw). A cell is clamped to min(w, maxw) either way.
static PyObject *offsets_to_matrix(PyObject *, PyObject *args) {
  Py_buffer data, offs;
  Py_ssize_t n, aoff, maxw, width = 0;
  if (!PyArg_ParseTuple(args, "y*y*nnn|n", &data, &offs, &n, &aoff, &maxw,
                        &width))
    return nullptr;
  if (maxw < 0) maxw = 0;  // python fallback: w = min(max_len, maxw) >= 0
  if (offs.len < static_cast<Py_ssize_t>((aoff + n + 1) * 8) ||
      n < 0 || aoff < 0) {
    PyBuffer_Release(&data);
    PyBuffer_Release(&offs);
    PyErr_SetString(PyExc_ValueError, "offsets buffer too small");
    return nullptr;
  }
  const int64_t *off = reinterpret_cast<const int64_t *>(offs.buf) + aoff;
  Py_ssize_t w = width;
  if (w <= 0) {
    int64_t wmax = 1;
    for (Py_ssize_t i = 0; i < n; i++) {
      int64_t li = off[i + 1] - off[i];
      if (li > wmax) wmax = li;
    }
    w = static_cast<Py_ssize_t>(wmax < maxw ? wmax : maxw);
  }
  const int64_t cap = w < maxw ? w : maxw;
  PyObject *mat = PyBytes_FromStringAndSize(nullptr, n * w);
  PyObject *lens_b = PyBytes_FromStringAndSize(nullptr, n * 4);
  PyObject *full_b = PyBytes_FromStringAndSize(nullptr, n * 8);
  if (!mat || !lens_b || !full_b) {
    PyBuffer_Release(&data);
    PyBuffer_Release(&offs);
    Py_XDECREF(mat);
    Py_XDECREF(lens_b);
    Py_XDECREF(full_b);
    return nullptr;
  }
  char *m = PyBytes_AS_STRING(mat);
  int32_t *lp = reinterpret_cast<int32_t *>(PyBytes_AS_STRING(lens_b));
  int64_t *fp = reinterpret_cast<int64_t *>(PyBytes_AS_STRING(full_b));
  const char *src = reinterpret_cast<const char *>(data.buf);
  bool ok = true;
  Py_BEGIN_ALLOW_THREADS;
  memset(m, 0, static_cast<size_t>(n * w));
  for (Py_ssize_t i = 0; i < n; i++) {
    int64_t start = off[i];
    int64_t li = off[i + 1] - start;
    if (start < 0 || li < 0 || start + li > data.len) {
      ok = false;
      break;
    }
    int64_t c = li < cap ? li : cap;
    memcpy(m + i * w, src + start, static_cast<size_t>(c));
    lp[i] = static_cast<int32_t>(c);
    fp[i] = li;
  }
  Py_END_ALLOW_THREADS;
  PyBuffer_Release(&data);
  PyBuffer_Release(&offs);
  if (!ok) {
    Py_DECREF(mat);
    Py_DECREF(lens_b);
    Py_DECREF(full_b);
    PyErr_SetString(PyExc_ValueError, "offsets out of data bounds");
    return nullptr;
  }
  return Py_BuildValue("(NNNn)", mat, lens_b, full_b, w);
}

// One partition's string leaves in ONE call: every column of an Arrow table
// slice -> its zero-padded [n, width] byte matrix + clamped int32 lens, the
// interpreter lock released once around all of it. A thread that cuts
// partitions beside a busy job thread (the source prefetch) pays one lock
// handoff a partition this way, not three a leaf (`offsets_to_matrix` after
// pyarrow's combine_chunks and cast, each of which lets the lock go and
// queues for it again).
//   columns: sequence of (pieces, width); pieces: sequence of
//            (data, offsets, first, count, large) — one per chunk the slice
//            touches: the chunk's data and offsets buffers (int64 offsets
//            where `large`, else int32), its first cell and cell count;
//            the counts of a column add up to n
//   returns ([(mat_bytes, lens_bytes), ...], over_bytes): over[i] = 1 where
//            a cell of row i is longer than min(width, maxw) (its row
//            boxes); None in its place where no cell is
struct CutPiece {
  Py_buffer data, offs;
  Py_ssize_t first, count;
  int large;
};

static PyObject *cut_strings(PyObject *, PyObject *args) {
  PyObject *cols_obj;
  Py_ssize_t n, maxw;
  if (!PyArg_ParseTuple(args, "Onn", &cols_obj, &n, &maxw)) return nullptr;
  if (maxw < 0) maxw = 0;
  PyObject *cols = PySequence_Fast(cols_obj, "columns must be a sequence");
  if (!cols) return nullptr;
  const Py_ssize_t k = PySequence_Fast_GET_SIZE(cols);
  std::vector<CutPiece> pieces;             // every column's, in order
  std::vector<Py_ssize_t> n_pieces(k), widths(k);
  PyObject *out = PyList_New(0);
  PyObject *over = n >= 0 ? PyBytes_FromStringAndSize(nullptr, n) : nullptr;
  bool ok = out && over;
  for (Py_ssize_t ci = 0; ok && ci < k; ci++) {
    PyObject *pcs_obj;
    Py_ssize_t w;
    if (!PyArg_ParseTuple(PySequence_Fast_GET_ITEM(cols, ci), "On", &pcs_obj,
                          &w)) {
      ok = false;
      break;
    }
    PyObject *pcs = PySequence_Fast(pcs_obj, "pieces must be a sequence");
    if (!pcs) {
      ok = false;
      break;
    }
    Py_ssize_t rows = 0;
    n_pieces[ci] = PySequence_Fast_GET_SIZE(pcs);
    widths[ci] = w;
    for (Py_ssize_t pi = 0; ok && pi < n_pieces[ci]; pi++) {
      CutPiece p;
      if (!PyArg_ParseTuple(PySequence_Fast_GET_ITEM(pcs, pi), "y*y*nnp",
                            &p.data, &p.offs, &p.first, &p.count, &p.large)) {
        ok = false;
        break;
      }
      pieces.push_back(p);
      const Py_ssize_t osz = p.large ? 8 : 4;
      if (p.first < 0 || p.count < 0 ||
          p.offs.len < (p.first + p.count + 1) * osz) {
        PyErr_SetString(PyExc_ValueError, "offsets buffer too small");
        ok = false;
      }
      rows += p.count;
    }
    Py_DECREF(pcs);
    if (ok && (rows != n || w < 1)) {
      PyErr_SetString(PyExc_ValueError,
                      "a column's pieces must hold n cells, at a width >= 1");
      ok = false;
    }
    if (ok) {
      PyObject *mat = PyBytes_FromStringAndSize(nullptr, n * w);
      PyObject *lens = PyBytes_FromStringAndSize(nullptr, n * 4);
      PyObject *pair = (mat && lens) ? PyTuple_Pack(2, mat, lens) : nullptr;
      Py_XDECREF(mat);
      Py_XDECREF(lens);
      if (!pair || PyList_Append(out, pair) < 0) ok = false;
      Py_XDECREF(pair);
    }
  }
  Py_DECREF(cols);
  if (ok) {
    // the buffers' addresses, taken with the lock held
    std::vector<char *> mats(k);
    std::vector<int32_t *> lens(k);
    for (Py_ssize_t ci = 0; ci < k; ci++) {
      PyObject *pair = PyList_GET_ITEM(out, ci);
      mats[ci] = PyBytes_AS_STRING(PyTuple_GET_ITEM(pair, 0));
      lens[ci] = reinterpret_cast<int32_t *>(
          PyBytes_AS_STRING(PyTuple_GET_ITEM(pair, 1)));
    }
    char *ov = PyBytes_AS_STRING(over);
    bool any_over = false;
    Py_BEGIN_ALLOW_THREADS;
    memset(ov, 0, static_cast<size_t>(n));
    size_t at = 0;
    for (Py_ssize_t ci = 0; ok && ci < k; ci++) {
      const Py_ssize_t w = widths[ci];
      const int64_t cap = w < maxw ? w : maxw;
      char *m = mats[ci];
      int32_t *lp = lens[ci];
      memset(m, 0, static_cast<size_t>(n * w));
      Py_ssize_t row = 0;
      for (Py_ssize_t pi = 0; ok && pi < n_pieces[ci]; pi++, at++) {
        const CutPiece &p = pieces[at];
        const char *src = reinterpret_cast<const char *>(p.data.buf);
        const int64_t *o64 = reinterpret_cast<const int64_t *>(p.offs.buf);
        const int32_t *o32 = reinterpret_cast<const int32_t *>(p.offs.buf);
        for (Py_ssize_t i = p.first; i < p.first + p.count; i++, row++) {
          const int64_t start = p.large ? o64[i] : o32[i];
          const int64_t li = (p.large ? o64[i + 1] : o32[i + 1]) - start;
          if (start < 0 || li < 0 || start + li > p.data.len) {
            ok = false;
            break;
          }
          const int64_t c = li < cap ? li : cap;
          memcpy(m + row * w, src + start, static_cast<size_t>(c));
          lp[row] = static_cast<int32_t>(c);
          if (li > cap) ov[row] = 1, any_over = true;
        }
      }
    }
    Py_END_ALLOW_THREADS;
    if (!ok) PyErr_SetString(PyExc_ValueError, "offsets out of data bounds");
    if (ok && !any_over) {
      Py_DECREF(over);
      over = Py_NewRef(Py_None);
    }
  }
  for (auto &p : pieces) {
    PyBuffer_Release(&p.data);
    PyBuffer_Release(&p.offs);
  }
  if (!ok) {
    Py_XDECREF(out);
    Py_XDECREF(over);
    if (!PyErr_Occurred())
      PyErr_SetString(PyExc_ValueError, "cut_strings: bad arguments");
    return nullptr;
  }
  return Py_BuildValue("(NN)", out, over);
}

// One partition's varlen payload (runtime/packing.PackedOuts) in ONE call:
// the rows of every entry lie back to back in `payload`, entry after entry,
// so the running offset is all the state there is — no cumsum, no offsets
// array — and the interpreter lock is released once around the partition.
//   entries: sequence of (lens, width, out) — lens: n int64 per-row byte
//            counts, each in [0, width]; out: a writable buffer of
//            n * max(width, 1) bytes, filled as the zero-padded
//            [n, max(width, 1)] matrix (every byte of it is written)
//   returns the payload bytes consumed; ValueError where a length lies
//            outside [0, width] or the rows run past the payload's end
struct VarlenEntry {
  Py_buffer lens, out;
  Py_ssize_t w;
};

static PyObject *unpack_varlen(PyObject *, PyObject *args) {
  Py_buffer payload;
  PyObject *entries_obj;
  if (!PyArg_ParseTuple(args, "y*O", &payload, &entries_obj)) return nullptr;
  PyObject *seq = PySequence_Fast(entries_obj, "entries must be a sequence");
  if (!seq) {
    PyBuffer_Release(&payload);
    return nullptr;
  }
  const Py_ssize_t k = PySequence_Fast_GET_SIZE(seq);
  std::vector<VarlenEntry> entries;
  entries.reserve(static_cast<size_t>(k));
  bool ok = true;
  for (Py_ssize_t ei = 0; ok && ei < k; ei++) {
    VarlenEntry e;
    PyObject *item = PySequence_Fast_GET_ITEM(seq, ei);
    if (!PyTuple_Check(item)) {
      PyErr_SetString(PyExc_TypeError, "an entry is a (lens, width, out)");
      ok = false;
      break;
    }
    if (!PyArg_ParseTuple(item, "y*nw*", &e.lens, &e.w, &e.out)) {
      ok = false;
      break;
    }
    entries.push_back(e);
    const Py_ssize_t n = e.lens.len / 8;
    if (e.w < 0 || e.lens.len % 8 || e.out.len != n * (e.w > 0 ? e.w : 1)) {
      PyErr_SetString(PyExc_ValueError,
                      "an entry needs n int64 lengths, a width >= 0 and "
                      "n * max(width, 1) bytes of output");
      ok = false;
    }
  }
  Py_DECREF(seq);
  int64_t at = 0;
  if (ok) {
    const char *src = reinterpret_cast<const char *>(payload.buf);
    const int64_t end = payload.len;
    Py_BEGIN_ALLOW_THREADS;
    for (size_t ei = 0; ok && ei < entries.size(); ei++) {
      const VarlenEntry &e = entries[ei];
      const int64_t w = e.w;
      const Py_ssize_t n = e.lens.len / 8;
      const int64_t *lp = reinterpret_cast<const int64_t *>(e.lens.buf);
      char *m = reinterpret_cast<char *>(e.out.buf);
      if (w == 0) memset(m, 0, static_cast<size_t>(n));
      for (Py_ssize_t i = 0; i < n; i++, m += w) {
        const int64_t li = lp[i];
        if (li < 0 || li > w || li > end - at) {
          ok = false;
          break;
        }
        if (w == 4 && !(li & 3)) {
          // the 4-byte kinds (a word or nothing): a load and a store
          uint32_t word = 0;
          if (li) memcpy(&word, src + at, 4);
          memcpy(m, &word, 4);
        } else {
          memcpy(m, src + at, static_cast<size_t>(li));
          memset(m + li, 0, static_cast<size_t>(w - li));
        }
        at += li;
      }
    }
    Py_END_ALLOW_THREADS;
    if (!ok)
      PyErr_SetString(PyExc_ValueError,
                      "a length outside [0, width], or rows past the "
                      "payload's end");
  }
  for (auto &e : entries) {
    PyBuffer_Release(&e.lens);
    PyBuffer_Release(&e.out);
  }
  PyBuffer_Release(&payload);
  if (!ok) return nullptr;
  return PyLong_FromLongLong(at);
}

static PyObject *decode_str(PyObject *, PyObject *args) {
  PyObject *mat_obj, *lens_obj;
  Py_ssize_t w, n;
  if (!PyArg_ParseTuple(args, "SSnn", &mat_obj, &lens_obj, &w, &n))
    return nullptr;
  const char *m = PyBytes_AS_STRING(mat_obj);
  const int32_t *lp =
      reinterpret_cast<const int32_t *>(PyBytes_AS_STRING(lens_obj));
  if (PyBytes_GET_SIZE(mat_obj) < n * w ||
      PyBytes_GET_SIZE(lens_obj) < n * 4) {
    PyErr_SetString(PyExc_ValueError, "buffer too small");
    return nullptr;
  }
  PyObject *out = PyList_New(n);
  if (!out) return nullptr;
  for (Py_ssize_t i = 0; i < n; i++) {
    int32_t li = lp[i];
    if (li < 0) li = 0;
    if (li > w) li = static_cast<int32_t>(w);
    PyObject *s =
        PyUnicode_DecodeUTF8(m + i * w, li, "replace");
    if (!s) {
      Py_DECREF(out);
      return nullptr;
    }
    PyList_SET_ITEM(out, i, s);
  }
  return out;
}

// One-pass multi-column decode: typed column buffers -> list of row tuples
// (list of bare values for a single column). The resultSetToCPython analog
// (reference: tuplex/python/src/PythonDataSet.cc:1400-1442 dispatches to
// per-type bulk decoders) — avoids per-column python lists, Option-mask
// comprehensions, and the final zip().
//
// spec per column: (kind, data_buf, valid_buf|None[, lens_buf, width])
//   kind: 0=i64 1=f64 2=bool 3=str(bytes matrix + i32 lens + width)
static PyObject *decode_columns(PyObject *, PyObject *args) {
  PyObject *spec;
  Py_ssize_t n;
  if (!PyArg_ParseTuple(args, "On", &spec, &n)) return nullptr;
  if (!PyList_Check(spec)) {
    PyErr_SetString(PyExc_TypeError, "spec must be a list");
    return nullptr;
  }
  Py_ssize_t k = PyList_GET_SIZE(spec);
  struct Col {
    int kind = 0;
    Py_buffer data{}, valid{}, lens{};
    bool has_valid = false, has_lens = false;
    Py_ssize_t w = 0;
  };
  std::vector<Col> cols(static_cast<size_t>(k));
  bool arg_ok = true;
  for (Py_ssize_t c = 0; c < k && arg_ok; c++) {
    PyObject *t = PyList_GET_ITEM(spec, c);
    Col &col = cols[static_cast<size_t>(c)];
    PyObject *vb = Py_None;
    long kind = 0;
    if (!PyArg_ParseTuple(t, "ly*|Oy*n", &kind, &col.data, &vb, &col.lens,
                          &col.w)) {
      col = Col{};  // ParseTuple released any y* buffers it acquired
      arg_ok = false;
      break;
    }
    col.kind = static_cast<int>(kind);
    col.has_lens = col.lens.buf != nullptr;
    if (vb != Py_None) {
      if (PyObject_GetBuffer(vb, &col.valid, PyBUF_SIMPLE) < 0) {
        arg_ok = false;
        break;
      }
      col.has_valid = true;
    }
    // bounds: every row index must stay inside the provided buffers; a
    // negative width would make `need` vacuously small and let
    // buf + i*w index backwards, so reject it outright (w == 0 is a
    // legal degenerate: every row decodes to the empty string)
    if (col.kind == 3 && col.w < 0) {
      PyErr_SetString(PyExc_ValueError, "string column width must be >= 0");
      arg_ok = false;
      break;
    }
    Py_ssize_t need = col.kind == 3 ? n * col.w
                      : col.kind == 2 ? n
                                      : n * 8;
    if (col.data.len < need || (col.has_valid && col.valid.len < n) ||
        (col.kind == 3 && (!col.has_lens || col.lens.len < n * 4))) {
      PyErr_SetString(PyExc_ValueError, "column buffer too small");
      arg_ok = false;
      break;
    }
  }
  PyObject *out = arg_ok ? PyList_New(n) : nullptr;
  if (out) {
    bool single = (k == 1);
    for (Py_ssize_t i = 0; i < n && out; i++) {
      PyObject *row = single ? nullptr : PyTuple_New(k);
      if (!single && !row) {
        Py_CLEAR(out);
        break;
      }
      for (Py_ssize_t c = 0; c < k; c++) {
        Col &col = cols[static_cast<size_t>(c)];
        PyObject *v = nullptr;
        if (col.has_valid &&
            !reinterpret_cast<const char *>(col.valid.buf)[i]) {
          v = Py_None;
          Py_INCREF(v);
        } else {
          switch (col.kind) {
            case 0:
              v = PyLong_FromLongLong(
                  reinterpret_cast<const int64_t *>(col.data.buf)[i]);
              break;
            case 1:
              v = PyFloat_FromDouble(
                  reinterpret_cast<const double *>(col.data.buf)[i]);
              break;
            case 2:
              v = PyBool_FromLong(
                  reinterpret_cast<const char *>(col.data.buf)[i]);
              break;
            case 3: {
              int32_t li = reinterpret_cast<const int32_t *>(col.lens.buf)[i];
              if (li < 0) li = 0;
              if (li > col.w) li = static_cast<int32_t>(col.w);
              v = PyUnicode_DecodeUTF8(
                  reinterpret_cast<const char *>(col.data.buf) + i * col.w,
                  li, "replace");
              break;
            }
            default:
              PyErr_SetString(PyExc_ValueError, "bad column kind");
          }
        }
        if (!v) {
          Py_XDECREF(row);
          Py_CLEAR(out);
          break;
        }
        if (single) {
          PyList_SET_ITEM(out, i, v);
        } else {
          PyTuple_SET_ITEM(row, c, v);
        }
      }
      if (out && !single) PyList_SET_ITEM(out, i, row);
    }
  }
  for (auto &col : cols) {
    if (col.data.buf) PyBuffer_Release(&col.data);
    if (col.has_valid) PyBuffer_Release(&col.valid);
    if (col.has_lens) PyBuffer_Release(&col.lens);
  }
  return out;
}

// One-pass mixed-tuple encode: list of k-tuples -> per-column typed buffers
// (the fastMixedSimpleTypeTupleTransfer analog, reference:
// tuplex/python/src/PythonContext.cc:860). kinds: same codes as
// decode_columns. Returns (cols, bad_list) where cols is a list of
//   i64/f64: (data_bytes, valid_bytes)   bool: (data_bytes, valid_bytes)
//   str:     (mat_bytes, lens_bytes, valid_bytes, width)
// bad rows (wrong arity / non-conforming field type / i64 overflow) have
// every column slot zeroed+valid and appear in bad_list for boxing.
static PyObject *encode_rows(PyObject *, PyObject *args) {
  PyObject *rows, *kinds_obj;
  if (!PyArg_ParseTuple(args, "OO", &rows, &kinds_obj)) return nullptr;
  if (!PyList_Check(rows) || !PyList_Check(kinds_obj)) {
    PyErr_SetString(PyExc_TypeError, "expected (list, list)");
    return nullptr;
  }
  Py_ssize_t n = PyList_GET_SIZE(rows);
  Py_ssize_t k = PyList_GET_SIZE(kinds_obj);
  std::vector<int> kinds(static_cast<size_t>(k));
  for (Py_ssize_t c = 0; c < k; c++) {
    long v = PyLong_AsLong(PyList_GET_ITEM(kinds_obj, c));
    if (v < 0 || v > 3) {
      PyErr_SetString(PyExc_ValueError, "bad kind");
      return nullptr;
    }
    kinds[static_cast<size_t>(c)] = static_cast<int>(v);
  }
  // str columns need a width pass first
  std::vector<Py_ssize_t> widths(static_cast<size_t>(k), 0);
  for (Py_ssize_t i = 0; i < n; i++) {
    PyObject *row = PyList_GET_ITEM(rows, i);
    // exact tuple only (matches the python path's `type(v) is tuple`):
    // namedtuple rows must box so collect() returns them unchanged
    if (!PyTuple_CheckExact(row) || PyTuple_GET_SIZE(row) != k) continue;
    for (Py_ssize_t c = 0; c < k; c++) {
      if (kinds[static_cast<size_t>(c)] != 3) continue;
      PyObject *o = PyTuple_GET_ITEM(row, c);
      if (PyUnicode_Check(o)) {
        Py_ssize_t sz = 0;
        if (PyUnicode_AsUTF8AndSize(o, &sz)) {
          if (sz > widths[static_cast<size_t>(c)])
            widths[static_cast<size_t>(c)] = sz;
        } else {
          PyErr_Clear();
        }
      }
    }
  }
  struct OutCol {
    PyObject *data = nullptr, *valid = nullptr, *lens = nullptr;
    char *d = nullptr, *v = nullptr;
    int32_t *lp = nullptr;
    Py_ssize_t w = 1;
  };
  std::vector<OutCol> out(static_cast<size_t>(k));
  bool alloc_ok = true;
  for (Py_ssize_t c = 0; c < k && alloc_ok; c++) {
    OutCol &oc = out[static_cast<size_t>(c)];
    int kind = kinds[static_cast<size_t>(c)];
    Py_ssize_t esz = kind == 2 ? 1 : 8;
    if (kind == 3) {
      oc.w = widths[static_cast<size_t>(c)] > 0
                 ? widths[static_cast<size_t>(c)]
                 : 1;
      oc.data = PyBytes_FromStringAndSize(nullptr, n * oc.w);
      oc.lens = PyBytes_FromStringAndSize(nullptr, n * 4);
      if (!oc.data || !oc.lens) {
        alloc_ok = false;
        break;
      }
      oc.lp = reinterpret_cast<int32_t *>(PyBytes_AS_STRING(oc.lens));
      memset(PyBytes_AS_STRING(oc.data), 0, static_cast<size_t>(n * oc.w));
    } else {
      oc.data = PyBytes_FromStringAndSize(nullptr, n * esz);
      if (!oc.data) {
        alloc_ok = false;
        break;
      }
      memset(PyBytes_AS_STRING(oc.data), 0, static_cast<size_t>(n * esz));
    }
    oc.valid = PyBytes_FromStringAndSize(nullptr, n);
    if (!oc.valid) {
      alloc_ok = false;
      break;
    }
    oc.d = PyBytes_AS_STRING(oc.data);
    oc.v = PyBytes_AS_STRING(oc.valid);
  }
  PyObject *bad_list = alloc_ok ? PyList_New(0) : nullptr;
  if (!bad_list) {
    for (auto &oc : out) {
      Py_XDECREF(oc.data);
      Py_XDECREF(oc.valid);
      Py_XDECREF(oc.lens);
    }
    return nullptr;
  }
  for (Py_ssize_t i = 0; i < n; i++) {
    PyObject *row = PyList_GET_ITEM(rows, i);
    bool bad = !PyTuple_CheckExact(row) || PyTuple_GET_SIZE(row) != k;
    for (Py_ssize_t c = 0; c < k && !bad; c++) {
      OutCol &oc = out[static_cast<size_t>(c)];
      PyObject *o = PyTuple_GET_ITEM(row, c);
      oc.v[i] = 1;
      if (o == Py_None) {
        oc.v[i] = 0;  // Option slot; schema-validity is the caller's check
        if (oc.lp) oc.lp[i] = 0;
        continue;
      }
      switch (kinds[static_cast<size_t>(c)]) {
        case 0: {
          if (!PyLong_Check(o) || PyBool_Check(o)) {
            bad = true;
            break;
          }
          int overflow = 0;
          long long val = PyLong_AsLongLongAndOverflow(o, &overflow);
          if (overflow) {
            bad = true;
            break;
          }
          reinterpret_cast<int64_t *>(oc.d)[i] = val;
          break;
        }
        case 1:
          if (!PyFloat_Check(o)) {
            bad = true;
            break;
          }
          reinterpret_cast<double *>(oc.d)[i] = PyFloat_AS_DOUBLE(o);
          break;
        case 2:
          if (!PyBool_Check(o)) {
            bad = true;
            break;
          }
          oc.d[i] = (o == Py_True) ? 1 : 0;
          break;
        case 3: {
          if (!PyUnicode_Check(o)) {
            bad = true;
            break;
          }
          Py_ssize_t sz = 0;
          const char *u = PyUnicode_AsUTF8AndSize(o, &sz);
          // sz > w can only happen if pass 1's AsUTF8 failed transiently
          // for this object — never write past the row slot
          if (!u || sz > oc.w) {
            PyErr_Clear();
            bad = true;
            break;
          }
          memcpy(oc.d + i * oc.w, u, static_cast<size_t>(sz));
          oc.lp[i] = static_cast<int32_t>(sz);
          break;
        }
      }
    }
    if (bad) {
      for (Py_ssize_t c = 0; c < k; c++) {
        OutCol &oc = out[static_cast<size_t>(c)];
        oc.v[i] = 1;  // slot unusable; caller boxes the row
        if (oc.lp) oc.lp[i] = 0;
      }
      PyObject *idx = PyLong_FromSsize_t(i);
      PyList_Append(bad_list, idx);
      Py_DECREF(idx);
    }
  }
  PyObject *cols_out = PyList_New(k);
  if (!cols_out) {
    for (auto &oc : out) {
      Py_XDECREF(oc.data);
      Py_XDECREF(oc.valid);
      Py_XDECREF(oc.lens);
    }
    Py_DECREF(bad_list);
    return nullptr;
  }
  for (Py_ssize_t c = 0; c < k; c++) {
    OutCol &oc = out[static_cast<size_t>(c)];
    PyObject *t =
        kinds[static_cast<size_t>(c)] == 3
            ? Py_BuildValue("(NNNn)", oc.data, oc.lens, oc.valid, oc.w)
            : Py_BuildValue("(NN)", oc.data, oc.valid);
    if (!t) {
      Py_DECREF(cols_out);
      Py_DECREF(bad_list);
      return nullptr;
    }
    PyList_SET_ITEM(cols_out, c, t);
  }
  return Py_BuildValue("(NN)", cols_out, bad_list);
}

static PyMethodDef Methods[] = {
    {"encode_i64", encode_i64, METH_O, "bulk encode int column"},
    {"encode_f64", encode_f64, METH_O, "bulk encode float column"},
    {"encode_bool", encode_bool, METH_O, "bulk encode bool column"},
    {"encode_str", encode_str, METH_O, "bulk encode str column"},
    {"offsets_to_matrix", offsets_to_matrix, METH_VARARGS,
     "arrow offsets+data -> padded byte matrix"},
    {"cut_strings", cut_strings, METH_VARARGS,
     "an arrow table slice's string columns -> padded byte matrices"},
    {"unpack_varlen", unpack_varlen, METH_VARARGS,
     "a packed partition's varlen payload -> padded byte matrices"},
    {"decode_str", decode_str, METH_VARARGS, "bulk decode str column"},
    {"decode_columns", decode_columns, METH_VARARGS,
     "typed column buffers -> list of row tuples"},
    {"encode_rows", encode_rows, METH_VARARGS,
     "list of tuples -> per-column typed buffers"},
    {nullptr, nullptr, 0, nullptr}};

static struct PyModuleDef Module = {PyModuleDef_HEAD_INIT, "_tuplex_native",
                                    "native host runtime kernels", -1,
                                    Methods};

}  // namespace

PyMODINIT_FUNC PyInit__tuplex_native(void) { return PyModule_Create(&Module); }
