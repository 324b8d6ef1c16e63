// ekjsoncol — native columnar JSON decoder for the ingest hot path.
//
// The TPU data plane wants columns, not dicts: the Python chain
// (json.loads -> list-of-dict -> per-column list comps, ~1.5us/row of
// GIL-bound work) caps full-pipe ingest far below the fused kernel's rate.
// This extension parses a run of raw JSON object payloads DIRECTLY into
// typed numpy columns + validity masks in one C pass:
//
//   decode(payloads: list[bytes], fields: ((name, type), ...), shards=1)
//     -> (columns: dict[str, ndarray], valid: dict[str, ndarray],
//         bad: ndarray[bool], (fields_kept, fields_skipped, bytes))
//   the last: the object members the parse met whose key is in `fields` (a
//   value was decoded) or is not (stepped over by skip_value: no value, no
//   StrRef, no Python object is ever built for it), and the payload bytes
//   it read.
//
// shards > 1 runs the GIL-free parse pass over `shards` contiguous slices
// of the payload list on native threads concurrently. Every shard writes
// into ITS row range of the one shared numpy allocation (rows are disjoint
// by construction — no per-shard buffers, no concat), keeps a private
// scratch/arena/StrRef list, and the final GIL'd intern pass walks shards
// in slice order so string interning (and therefore the output) is
// byte-identical to the single-thread path for any shard count.
//
// field types: 0=FLOAT(f32) 1=BIGINT(i64) 2=BOOLEAN(bool) 3=STRING(object)
// Semantics mirror data/cast.py CONVERT_ALL coercion (the row-path
// preprocessor): numeric strings parse, bools in {0,1} accept, numbers
// stringify with shortest round-trip (to_chars), null/missing -> invalid,
// uncastable value -> row marked bad (caller drops it). Rows that need
// semantics C can't reproduce (int64 overflow -> Python bigint) flag the
// whole batch for Python fallback by raising ekjsoncol.Fallback.
//
// Repeated string values (10k device ids over millions of rows) intern
// through a local hash table, so the object column mostly holds INCREF'd
// existing PyUnicode objects instead of fresh allocations.
//
// Reference analogue: the schema-aware fastjson converter
// (internal/converter/json) feeding SliceTuple columns.
#define PY_SSIZE_T_CLEAN
#include <Python.h>
#define NPY_NO_DEPRECATED_API NPY_1_7_API_VERSION
#include <numpy/arrayobject.h>

#include <cctype>
#include <charconv>
#include <cmath>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <deque>
#include <string>
#include <thread>
#include <unordered_map>
#include <vector>

namespace {

enum FieldType { F_FLOAT = 0, F_BIGINT = 1, F_BOOL = 2, F_STRING = 3 };

struct Field {
  std::string name;
  int type;
  // output buffers (borrowed from the numpy arrays)
  float* f32 = nullptr;
  int64_t* i64 = nullptr;
  unsigned char* b8 = nullptr;
  PyObject** obj = nullptr;
  unsigned char* valid = nullptr;
};

struct StrKey {
  const char* p;
  size_t n;
  bool operator==(const StrKey& o) const {
    return n == o.n && std::memcmp(p, o.p, n) == 0;
  }
};
struct StrKeyHash {
  size_t operator()(const StrKey& k) const {
    // FNV-1a
    size_t h = 1469598103934665603ull;
    for (size_t i = 0; i < k.n; i++) {
      h ^= (unsigned char)k.p[i];
      h *= 1099511628211ull;
    }
    return h;
  }
};

struct Parser {
  const char* p;
  const char* end;
  bool fallback = false;  // batch needs the Python path
  std::string scratch;    // unescape buffer

  explicit Parser(const char* b, const char* e) : p(b), end(e) {}

  void ws() {
    while (p < end && (*p == ' ' || *p == '\t' || *p == '\n' || *p == '\r'))
      p++;
  }
  bool lit(const char* s, size_t n) {
    if ((size_t)(end - p) < n || std::memcmp(p, s, n) != 0) return false;
    p += n;
    return true;
  }

  // Parse a JSON string (after the opening quote). Returns pointer/len of
  // the decoded content — either a borrowed range of the input (no escapes,
  // the common case) or `scratch`.
  bool str_body(const char** out, size_t* out_n) {
    const char* start = p;
    while (p < end && *p != '"' && *p != '\\') p++;
    if (p < end && *p == '"') {  // fast path: no escapes
      *out = start;
      *out_n = (size_t)(p - start);
      p++;
      return true;
    }
    // slow path: unescape into scratch
    scratch.assign(start, (size_t)(p - start));
    while (p < end && *p != '"') {
      if (*p != '\\') {
        scratch.push_back(*p++);
        continue;
      }
      p++;
      if (p >= end) return false;
      char c = *p++;
      switch (c) {
        case '"': scratch.push_back('"'); break;
        case '\\': scratch.push_back('\\'); break;
        case '/': scratch.push_back('/'); break;
        case 'b': scratch.push_back('\b'); break;
        case 'f': scratch.push_back('\f'); break;
        case 'n': scratch.push_back('\n'); break;
        case 'r': scratch.push_back('\r'); break;
        case 't': scratch.push_back('\t'); break;
        case 'u': {
          if (end - p < 4) return false;
          unsigned cp = 0;
          for (int i = 0; i < 4; i++) {
            char h = *p++;
            cp <<= 4;
            if (h >= '0' && h <= '9') cp |= (unsigned)(h - '0');
            else if (h >= 'a' && h <= 'f') cp |= (unsigned)(h - 'a' + 10);
            else if (h >= 'A' && h <= 'F') cp |= (unsigned)(h - 'A' + 10);
            else return false;
          }
          if (cp >= 0xD800 && cp <= 0xDBFF && end - p >= 6 && p[0] == '\\' &&
              p[1] == 'u') {  // surrogate pair
            unsigned lo = 0;
            const char* q = p + 2;
            bool ok = true;
            for (int i = 0; i < 4; i++) {
              char h = q[i];
              lo <<= 4;
              if (h >= '0' && h <= '9') lo |= (unsigned)(h - '0');
              else if (h >= 'a' && h <= 'f') lo |= (unsigned)(h - 'a' + 10);
              else if (h >= 'A' && h <= 'F') lo |= (unsigned)(h - 'A' + 10);
              else { ok = false; break; }
            }
            if (ok && lo >= 0xDC00 && lo <= 0xDFFF) {
              cp = 0x10000 + ((cp - 0xD800) << 10) + (lo - 0xDC00);
              p = q + 4;
            }
          }
          // utf-8 encode
          if (cp < 0x80) scratch.push_back((char)cp);
          else if (cp < 0x800) {
            scratch.push_back((char)(0xC0 | (cp >> 6)));
            scratch.push_back((char)(0x80 | (cp & 0x3F)));
          } else if (cp < 0x10000) {
            scratch.push_back((char)(0xE0 | (cp >> 12)));
            scratch.push_back((char)(0x80 | ((cp >> 6) & 0x3F)));
            scratch.push_back((char)(0x80 | (cp & 0x3F)));
          } else {
            scratch.push_back((char)(0xF0 | (cp >> 18)));
            scratch.push_back((char)(0x80 | ((cp >> 12) & 0x3F)));
            scratch.push_back((char)(0x80 | ((cp >> 6) & 0x3F)));
            scratch.push_back((char)(0x80 | (cp & 0x3F)));
          }
          break;
        }
        default: return false;
      }
    }
    if (p >= end) return false;
    p++;  // closing quote
    *out = scratch.data();
    *out_n = scratch.size();
    return true;
  }

  // Skip any JSON value (for undeclared keys).
  bool skip_value() {
    ws();
    if (p >= end) return false;
    char c = *p;
    if (c == '"') {
      p++;
      const char* s;
      size_t n;
      return str_body(&s, &n);
    }
    if (c == '{' || c == '[') {
      char open = c, close = (c == '{') ? '}' : ']';
      int depth = 0;
      bool in_str = false;
      while (p < end) {
        char d = *p++;
        if (in_str) {
          if (d == '\\') { if (p < end) p++; }
          else if (d == '"') in_str = false;
        } else if (d == '"') in_str = true;
        else if (d == open) depth++;
        else if (d == close) {
          if (--depth == 0) return true;
        }
      }
      return false;
    }
    if (lit("true", 4) || lit("false", 5) || lit("null", 4)) return true;
    // number ('+'-prefixed forms are not JSON — json.loads rejects them)
    if (p < end && *p == '+') return false;
    const char* start = p;
    if (p < end && *p == '-') p++;
    while (p < end && (std::isdigit((unsigned char)*p) || *p == '.' ||
                       *p == 'e' || *p == 'E' || *p == '-' || *p == '+'))
      p++;
    return p > start;
  }
};

// shortest-round-trip double -> string, matching Python str(float) closely
void format_double(double v, std::string& out) {
#if defined(__cpp_lib_to_chars) && __cpp_lib_to_chars >= 201611L
  char buf[40];
  auto res = std::to_chars(buf, buf + sizeof(buf), v);
  out.assign(buf, res.ptr);
#else
  // no floating-point to_chars (GCC < 11): smallest %g precision that
  // parses back to exactly v — same shortest-round-trip contract
  char buf[40];
  for (int prec = 1; prec <= 17; prec++) {
    std::snprintf(buf, sizeof(buf), "%.*g", prec, v);
    if (std::strtod(buf, nullptr) == v) break;
  }
  out = buf;
#endif
}

struct Interner {
  std::unordered_map<StrKey, PyObject*, StrKeyHash> map;
  // owns key bytes — deque: element addresses are STABLE across growth
  // (a vector reallocation would move SSO strings and dangle StrKey.p)
  std::deque<std::string> storage;
  bool bad_utf8 = false;  // last get() failed UTF-8 validation (bad row)

  ~Interner() {
    for (auto& kv : map) Py_DECREF(kv.second);
  }
  PyObject* get(const char* s, size_t n) {  // returns NEW reference
    auto it = map.find(StrKey{s, n});
    if (it != map.end()) {
      Py_INCREF(it->second);
      return it->second;
    }
    // json.loads preserves lone \u-escape surrogates but raises on other
    // invalid UTF-8; surrogatepass mirrors that so both decode paths
    // classify the same payloads as bad (the Python path drops the row)
    PyObject* u = PyUnicode_DecodeUTF8(s, (Py_ssize_t)n, "surrogatepass");
    if (u == nullptr) {
      if (PyErr_ExceptionMatches(PyExc_UnicodeDecodeError)) {
        PyErr_Clear();
        bad_utf8 = true;
      }
      return nullptr;
    }
    if (map.size() < 262144) {  // bound the table
      storage.emplace_back(s, n);
      const std::string& owned = storage.back();
      Py_INCREF(u);
      map.emplace(StrKey{owned.data(), owned.size()}, u);
    }
    return u;
  }
};

// A string value discovered during the GIL-free parse pass: the row/field
// it belongs to and a byte span that stays valid until the GIL'd intern
// pass (either borrowed payload bytes or arena-owned unescaped bytes).
struct StrRef {
  npy_intp row;
  int field;
  const char* p;
  size_t n;
};

// Owns bytes for escaped/converted string values across the two passes.
// deque keeps element addresses stable under growth.
struct Arena {
  std::deque<std::string> items;
  const char* put(const char* s, size_t n) {
    items.emplace_back(s, n);
    return items.back().data();
  }
  const char* put(const std::string& s) {
    items.emplace_back(s);
    return items.back().data();
  }
};

// What one parse pass met: members decoded into a column, members stepped
// over because no field of the spec bears their key.
struct Tally {
  uint64_t kept = 0;
  uint64_t skipped = 0;
};

// Parse one object payload into row r of the field buffers.
// Returns: 0 ok, 1 bad row (cast/shape error), 2 batch fallback.
// Runs WITHOUT the GIL: string values are recorded as StrRefs (payload
// spans or arena copies) and materialized in a later GIL'd intern pass.
int parse_row(Parser& ps, std::vector<Field>& fields, npy_intp r,
              std::vector<StrRef>& strs, Arena& arena, std::string& tmp,
              Tally& tally) {
  ps.ws();
  if (ps.p < ps.end && *ps.p == '[')
    return 2;  // array payload: rows-per-payload is the python path's job
  if (ps.p >= ps.end || *ps.p != '{') return 1;
  ps.p++;
  ps.ws();
  if (ps.p < ps.end && *ps.p == '}') {
    ps.p++;
    ps.ws();
    return (ps.p == ps.end) ? 0 : 1;  // '{} garbage' is NOT a good row
  }
  while (true) {
    ps.ws();
    if (ps.p >= ps.end || *ps.p != '"') return 1;
    ps.p++;
    const char* key;
    size_t key_n;
    {
      // key may come from scratch; copy before value parsing reuses it
      const char* k;
      size_t kn;
      if (!ps.str_body(&k, &kn)) return 1;
      if (k == ps.scratch.data()) {
        tmp.assign(k, kn);
        key = tmp.data();
      } else {
        key = k;
      }
      key_n = kn;
    }
    ps.ws();
    if (ps.p >= ps.end || *ps.p != ':') return 1;
    ps.p++;
    Field* f = nullptr;
    for (auto& cand : fields) {
      if (cand.name.size() == key_n &&
          std::memcmp(cand.name.data(), key, key_n) == 0) {
        f = &cand;
        break;
      }
    }
    if (f == nullptr) {
      tally.skipped++;
      if (!ps.skip_value()) return 1;
    } else {
      tally.kept++;
      ps.ws();
      if (ps.p >= ps.end) return 1;
      char c = *ps.p;
      if (c == 'n' && ps.lit("null", 4)) {
        // null -> invalid (valid[r] stays 0)
      } else if (c == '{' || c == '[') {
        return 1;  // nested value for a scalar field: cast error -> drop
      } else if (c == '"') {
        ps.p++;
        const char* s;
        size_t n;
        if (!ps.str_body(&s, &n)) return 1;
        switch (f->type) {
          case F_STRING: {
            // UTF-8 validity is checked at intern time (GIL pass); escaped
            // content lives in ps.scratch which the next string reuses, so
            // copy it into the arena now
            const char* sp = (s == ps.scratch.data()) ? arena.put(s, n) : s;
            strs.push_back({r, (int)(f - fields.data()), sp, n});
            f->valid[r] = 1;
            break;
          }
          case F_FLOAT: case F_BIGINT: {
            // cast.to_float/to_int accept numeric strings (CONVERT_ALL)
            tmp.assign(s, n);
            char* endp = nullptr;
            double v = std::strtod(tmp.c_str(), &endp);
            if (endp == tmp.c_str() || *endp != '\0') return 1;
            if (f->type == F_FLOAT) f->f32[r] = (float)v;
            else {
              if (v > 9.2233720368547e18 || v < -9.2233720368547e18)
                return 2;  // beyond int64: Python bigint semantics
              f->i64[r] = (int64_t)v;
            }
            f->valid[r] = 1;
            break;
          }
          case F_BOOL: {
            // to_bool(str): lowercase match on true/false/1/0
            std::string low(s, n);
            for (auto& ch : low) ch = (char)std::tolower((unsigned char)ch);
            if (low == "true" || low == "1") f->b8[r] = 1;
            else if (low == "false" || low == "0") f->b8[r] = 0;
            else return 1;
            f->valid[r] = 1;
            break;
          }
        }
      } else if (c == 't' || c == 'f') {
        bool v = (c == 't');
        if (!(v ? ps.lit("true", 4) : ps.lit("false", 5))) return 1;
        switch (f->type) {
          case F_BOOL: f->b8[r] = v ? 1 : 0; break;
          case F_FLOAT: f->f32[r] = v ? 1.0f : 0.0f; break;  // to_float(bool)
          case F_BIGINT: f->i64[r] = v ? 1 : 0; break;       // to_int(bool)
          case F_STRING: {
            strs.push_back({r, (int)(f - fields.data()),
                            v ? "true" : "false", v ? 4u : 5u});
            break;
          }
        }
        f->valid[r] = 1;
      } else {
        // number ('+'-prefixed forms are not JSON — json.loads rejects them)
        if (*ps.p == '+') return 1;
        const char* start = ps.p;
        if (*ps.p == '-') ps.p++;
        bool is_float = false;
        while (ps.p < ps.end &&
               (std::isdigit((unsigned char)*ps.p) || *ps.p == '.' ||
                *ps.p == 'e' || *ps.p == 'E' || *ps.p == '-' || *ps.p == '+')) {
          if (*ps.p == '.' || *ps.p == 'e' || *ps.p == 'E') is_float = true;
          ps.p++;
        }
        if (ps.p == start) return 1;
        tmp.assign(start, (size_t)(ps.p - start));
        switch (f->type) {
          case F_FLOAT: {
            char* endp = nullptr;
            double v = std::strtod(tmp.c_str(), &endp);
            if (*endp != '\0') return 1;
            f->f32[r] = (float)v;
            break;
          }
          case F_BIGINT: {
            if (!is_float) {
              errno = 0;
              char* endp = nullptr;
              long long v = std::strtoll(tmp.c_str(), &endp, 10);
              if (*endp != '\0') return 1;
              if (errno == ERANGE) return 2;  // Python bigint territory
              f->i64[r] = v;
            } else {
              char* endp = nullptr;
              double v = std::strtod(tmp.c_str(), &endp);
              if (*endp != '\0') return 1;
              if (v > 9.2233720368547e18 || v < -9.2233720368547e18) return 2;
              f->i64[r] = (int64_t)v;  // to_int truncates
            }
            break;
          }
          case F_BOOL: {
            // to_bool accepts numeric values equal to 0 or 1 only
            char* endp = nullptr;
            double v = std::strtod(tmp.c_str(), &endp);
            if (*endp != '\0' || (v != 0.0 && v != 1.0)) return 1;
            f->b8[r] = (v == 1.0) ? 1 : 0;
            break;
          }
          case F_STRING: {
            // to_string: integral floats render as ints, else str(float)
            std::string sv;
            if (!is_float) sv = tmp;
            else {
              char* endp = nullptr;
              double v = std::strtod(tmp.c_str(), &endp);
              if (*endp != '\0') return 1;
              if (std::isfinite(v) && v == std::floor(v) &&
                  std::fabs(v) < 9.2e18) {
                char b[32];
                auto res = std::to_chars(b, b + sizeof(b), (long long)v);
                sv.assign(b, res.ptr);
              } else {
                format_double(v, sv);
              }
            }
            strs.push_back({r, (int)(f - fields.data()),
                            arena.put(sv), sv.size()});
            break;
          }
        }
        f->valid[r] = 1;
      }
    }
    ps.ws();
    if (ps.p < ps.end && *ps.p == ',') { ps.p++; continue; }
    if (ps.p < ps.end && *ps.p == '}') { ps.p++; break; }
    return 1;
  }
  ps.ws();
  return (ps.p == ps.end) ? 0 : 1;  // trailing garbage -> bad row
}

PyObject* FallbackError = nullptr;

// Per-shard private parse state: everything the GIL-free pass touches that
// is not a disjoint row range of the shared output buffers.
struct Shard {
  npy_intp begin = 0;
  npy_intp end = 0;
  std::vector<StrRef> strs;
  Arena arena;
  Tally tally;
  bool fallback = false;
};

// Parse rows [sh.begin, sh.end) of the payload slice. Pure native code —
// runs with the GIL released, possibly on a std::thread.
void parse_shard(Shard& sh,
                 const std::vector<std::pair<const char*, Py_ssize_t>>& bufs,
                 std::vector<Field>& fields, unsigned char* bad) {
  std::string tmp;
  sh.strs.reserve((size_t)(sh.end - sh.begin));
  for (npy_intp r = sh.begin; r < sh.end; r++) {
    Parser ps(bufs[(size_t)r].first,
              bufs[(size_t)r].first + bufs[(size_t)r].second);
    int rc = parse_row(ps, fields, r, sh.strs, sh.arena, tmp, sh.tally);
    if (rc == 2) {
      sh.fallback = true;
      break;
    }
    if (rc == 1) {
      bad[r] = 1;
      for (auto& f : fields) f.valid[r] = 0;
    }
  }
}

PyObject* jc_decode(PyObject*, PyObject* args) {
  PyObject* payloads;
  PyObject* fields_spec;
  int n_shards = 1;
  if (!PyArg_ParseTuple(args, "OO|i", &payloads, &fields_spec, &n_shards))
    return nullptr;
  if (!PyList_Check(payloads) || !PyTuple_Check(fields_spec)) {
    PyErr_SetString(PyExc_TypeError, "decode(list[bytes], tuple[(name, type)])");
    return nullptr;
  }
  npy_intp n_rows = (npy_intp)PyList_GET_SIZE(payloads);
  Py_ssize_t n_fields = PyTuple_GET_SIZE(fields_spec);

  std::vector<Field> fields((size_t)n_fields);
  PyObject* cols = PyDict_New();
  PyObject* valids = PyDict_New();
  for (Py_ssize_t i = 0; i < n_fields; i++) {
    PyObject* spec = PyTuple_GET_ITEM(fields_spec, i);
    const char* name;
    int ftype;
    if (!PyArg_ParseTuple(spec, "si", &name, &ftype)) {
      Py_DECREF(cols); Py_DECREF(valids);
      return nullptr;
    }
    Field& f = fields[(size_t)i];
    f.name = name;
    f.type = ftype;
    int npy_type;
    switch (ftype) {
      case F_FLOAT: npy_type = NPY_FLOAT32; break;
      case F_BIGINT: npy_type = NPY_INT64; break;
      case F_BOOL: npy_type = NPY_BOOL; break;
      case F_STRING: npy_type = NPY_OBJECT; break;
      default:
        PyErr_SetString(PyExc_ValueError, "bad field type");
        Py_DECREF(cols); Py_DECREF(valids);
        return nullptr;
    }
    PyObject* arr = PyArray_ZEROS(1, &n_rows, npy_type, 0);
    PyObject* va = PyArray_ZEROS(1, &n_rows, NPY_BOOL, 0);
    if (arr == nullptr || va == nullptr) {
      Py_XDECREF(arr); Py_XDECREF(va);
      Py_DECREF(cols); Py_DECREF(valids);
      return nullptr;
    }
    void* data = PyArray_DATA((PyArrayObject*)arr);
    switch (ftype) {
      case F_FLOAT: f.f32 = (float*)data; break;
      case F_BIGINT: f.i64 = (int64_t*)data; break;
      case F_BOOL: f.b8 = (unsigned char*)data; break;
      case F_STRING: f.obj = (PyObject**)data; break;
    }
    f.valid = (unsigned char*)PyArray_DATA((PyArrayObject*)va);
    PyDict_SetItemString(cols, name, arr);
    PyDict_SetItemString(valids, name, va);
    Py_DECREF(arr);
    Py_DECREF(va);
  }
  PyObject* bad_arr = PyArray_ZEROS(1, &n_rows, NPY_BOOL, 0);
  if (bad_arr == nullptr) {
    Py_DECREF(cols); Py_DECREF(valids);
    return nullptr;
  }
  unsigned char* bad = (unsigned char*)PyArray_DATA((PyArrayObject*)bad_arr);

  // NaN-fill float columns (invalid rows must read as NaN, matching
  // from_messages); object columns pre-fill with None
  for (auto& f : fields) {
    if (f.type == F_FLOAT) {
      for (npy_intp r = 0; r < n_rows; r++) f.f32[r] = NAN;
    } else if (f.type == F_STRING) {
      for (npy_intp r = 0; r < n_rows; r++) {
        Py_INCREF(Py_None);
        f.obj[r] = Py_None;
      }
    }
  }

  // resolve payload buffers under the GIL; the caller owns the list and
  // must not mutate it during the call (the source's flush list is local).
  // bytes are immutable so borrowing their buffer across the GIL release
  // is safe; bytearrays can be resized by another thread (realloc frees
  // the buffer the parse would read) — copy those now, while we hold it.
  std::vector<std::pair<const char*, Py_ssize_t>> bufs((size_t)n_rows);
  Arena payload_copies;
  for (npy_intp r = 0; r < n_rows; r++) {
    PyObject* pl = PyList_GET_ITEM(payloads, r);
    if (PyBytes_Check(pl)) {
      bufs[(size_t)r] = {PyBytes_AS_STRING(pl), PyBytes_GET_SIZE(pl)};
    } else if (PyByteArray_Check(pl)) {
      Py_ssize_t bn = PyByteArray_GET_SIZE(pl);
      bufs[(size_t)r] = {
          payload_copies.put(PyByteArray_AS_STRING(pl), (size_t)bn), bn};
    } else {
      Py_DECREF(cols); Py_DECREF(valids); Py_DECREF(bad_arr);
      PyErr_SetString(FallbackError, "non-bytes payload");
      return nullptr;
    }
  }

  // pass 1 — parse WITHOUT the GIL: numeric/bool columns fill directly,
  // string values become StrRefs. This is the bulk of the work and runs
  // truly parallel to the engine's other Python threads (the fused node
  // worker, emit workers), which is what lets a byte-fed pipe keep the
  // device path busy (reference measures bytes-in end-to-end, README.md:98).
  // With shards > 1 the pass itself also fans out over native threads:
  // each shard owns a contiguous row slice of the SAME output buffers.
  if (n_shards < 1) n_shards = 1;
  if (n_shards > 32) n_shards = 32;
  // tiny batches: thread spawn would cost more than the parse
  while (n_shards > 1 && n_rows < (npy_intp)n_shards * 256) n_shards--;
  std::vector<Shard> shards((size_t)n_shards);
  {
    npy_intp chunk = (n_rows + n_shards - 1) / n_shards;
    for (int i = 0; i < n_shards; i++) {
      shards[(size_t)i].begin = std::min((npy_intp)i * chunk, n_rows);
      shards[(size_t)i].end = std::min((npy_intp)(i + 1) * chunk, n_rows);
    }
  }
  bool need_fallback = false;
  Py_BEGIN_ALLOW_THREADS
  if (n_shards == 1) {
    parse_shard(shards[0], bufs, fields, bad);
  } else {
    std::vector<std::thread> workers;
    workers.reserve((size_t)(n_shards - 1));
    try {
      for (int i = 1; i < n_shards; i++)
        workers.emplace_back(parse_shard, std::ref(shards[(size_t)i]),
                             std::cref(bufs), std::ref(fields), bad);
    } catch (const std::exception&) {
      // thread/resource exhaustion (EAGAIN): the un-spawned shards run
      // serially below — a slower decode, never a std::terminate (and
      // never an exception escaping the no-GIL region)
    }
    parse_shard(shards[0], bufs, fields, bad);
    for (size_t i = workers.size() + 1; i < (size_t)n_shards; i++)
      parse_shard(shards[i], bufs, fields, bad);
    for (auto& w : workers) w.join();
  }
  for (auto& sh : shards)
    if (sh.fallback) need_fallback = true;
  Py_END_ALLOW_THREADS
  if (need_fallback) {
    Py_DECREF(cols); Py_DECREF(valids); Py_DECREF(bad_arr);
    PyErr_SetString(FallbackError, "payload needs the python decoder");
    return nullptr;
  }

  // pass 2 — intern string values under the GIL: hash + incref per value
  // (hit path), PyUnicode decode only for novel strings. Invalid UTF-8
  // marks the row bad (json.loads parity), never a batch fallback.
  // Shards are walked in slice order, so the intern sequence (and the
  // bounded table's contents) matches the single-thread pass exactly.
  Interner intern;
  for (auto& sh : shards) {
    for (const StrRef& sr : sh.strs) {
      if (bad[sr.row]) continue;  // a later field already failed this row
      PyObject* u = intern.get(sr.p, sr.n);
      if (u == nullptr) {
        if (intern.bad_utf8) {
          intern.bad_utf8 = false;
          bad[sr.row] = 1;
          for (auto& f : fields) f.valid[sr.row] = 0;
          continue;
        }
        Py_DECREF(cols); Py_DECREF(valids); Py_DECREF(bad_arr);
        return nullptr;  // real error (e.g. MemoryError) already set
      }
      Field& f = fields[(size_t)sr.field];
      Py_XDECREF(f.obj[sr.row]);
      f.obj[sr.row] = u;
    }
  }
  Tally all;
  unsigned long long n_bytes = 0;
  for (auto& sh : shards) {
    all.kept += sh.tally.kept;
    all.skipped += sh.tally.skipped;
  }
  for (auto& b : bufs) n_bytes += (unsigned long long)b.second;
  PyObject* out = nullptr;
  PyObject* tally = Py_BuildValue("(KKK)", (unsigned long long)all.kept,
                                  (unsigned long long)all.skipped, n_bytes);
  if (tally != nullptr) {
    out = PyTuple_Pack(4, cols, valids, bad_arr, tally);
    Py_DECREF(tally);
  }
  Py_DECREF(cols);
  Py_DECREF(valids);
  Py_DECREF(bad_arr);
  return out;
}

// ---------------------------------------------------------------------------
// Persistent per-stream key-slot table (GROUP BY dictionary encode).
//
// The Python KeyTable's steady-state encode is a C-level dict map per row
// (~7 ms per 64k batch) serialized on the fused worker thread. keytab_*
// moves that walk into one native pass over the decoded key column: a
// persistent byte-keyed hash table (key bytes -> dense int32 slot) plus a
// bounded pointer-identity cache over the interned PyUnicode objects the
// decoder emits (repeated device ids resolve by pointer hash, no byte
// compare). Newly-seen keys return as an ordered appendix so the Python
// KeyTable — which STAYS the source of truth for reverse decode,
// checkpointing, and every fallback path — bulk-syncs to identical slot
// ids. Normalization matches KeyTable._normalize: None encodes as "".
//
// Contract: encode(tab, keys_list) either completes fully or raises
// WITHOUT mutating the table (non-str/None elements, lone-surrogate
// strings -> ekjsoncol.Fallback; the caller runs the Python path).

struct KeyTab {
  std::unordered_map<StrKey, int32_t, StrKeyHash> byte_map;
  std::deque<std::string> storage;  // owns key bytes; stable addresses
  std::unordered_map<PyObject*, int32_t> ptr_cache;  // strong refs
  int64_t n = 0;  // slots assigned == byte_map.size()

  ~KeyTab() {
    // capsule destructors can run during interpreter teardown, when
    // touching refcounts is no longer safe
    if (Py_IsInitialized()) {
      for (auto& kv : ptr_cache) Py_DECREF(kv.first);
    }
  }
};

constexpr size_t kPtrCacheCap = 1u << 16;

void keytab_destruct(PyObject* cap) {
  auto* kt = (KeyTab*)PyCapsule_GetPointer(cap, "ekjsoncol.keytab");
  delete kt;
}

KeyTab* keytab_from(PyObject* cap) {
  return (KeyTab*)PyCapsule_GetPointer(cap, "ekjsoncol.keytab");
}

PyObject* kt_new(PyObject*, PyObject*) {
  return PyCapsule_New(new KeyTab(), "ekjsoncol.keytab", keytab_destruct);
}

PyObject* kt_len(PyObject*, PyObject* args) {
  PyObject* cap;
  if (!PyArg_ParseTuple(args, "O", &cap)) return nullptr;
  KeyTab* kt = keytab_from(cap);
  if (kt == nullptr) return nullptr;
  return PyLong_FromLongLong((long long)kt->n);
}

PyObject* kt_clear(PyObject*, PyObject* args) {
  PyObject* cap;
  if (!PyArg_ParseTuple(args, "O", &cap)) return nullptr;
  KeyTab* kt = keytab_from(cap);
  if (kt == nullptr) return nullptr;
  for (auto& kv : kt->ptr_cache) Py_DECREF(kv.first);
  kt->ptr_cache.clear();
  kt->byte_map.clear();
  kt->storage.clear();
  kt->n = 0;
  Py_RETURN_NONE;
}

PyObject* kt_encode(PyObject*, PyObject* args) {
  PyObject* cap;
  PyObject* seq;
  if (!PyArg_ParseTuple(args, "OO", &cap, &seq)) return nullptr;
  KeyTab* kt = keytab_from(cap);
  if (kt == nullptr) return nullptr;
  PyObject* fast = PySequence_Fast(seq, "keytab_encode expects a sequence");
  if (fast == nullptr) return nullptr;
  npy_intp n = (npy_intp)PySequence_Fast_GET_SIZE(fast);
  PyObject** items = PySequence_Fast_ITEMS(fast);

  // pass 1 — validate + resolve key bytes BEFORE any table mutation, so a
  // reject leaves the table byte-identical to the Python-path history.
  // Exact str / None only: subclasses (np.str_) or other types keep the
  // Python dict semantics the native map can't reproduce.
  std::vector<std::pair<const char*, Py_ssize_t>> spans((size_t)n);
  for (npy_intp i = 0; i < n; i++) {
    PyObject* it = items[i];
    if (it == Py_None) {
      spans[(size_t)i] = {"", 0};  // KeyTable._normalize: None -> ""
      continue;
    }
    if (!PyUnicode_CheckExact(it)) {
      Py_DECREF(fast);
      PyErr_SetString(FallbackError, "non-string key");
      return nullptr;
    }
    Py_ssize_t sn = 0;
    const char* sp = PyUnicode_AsUTF8AndSize(it, &sn);
    if (sp == nullptr) {  // lone surrogates: not UTF-8 encodable
      PyErr_Clear();
      Py_DECREF(fast);
      PyErr_SetString(FallbackError, "non-encodable key");
      return nullptr;
    }
    spans[(size_t)i] = {sp, sn};
  }

  PyObject* slots_arr = PyArray_SimpleNew(1, &n, NPY_INT32);
  PyObject* appendix = PyList_New(0);
  if (slots_arr == nullptr || appendix == nullptr) {
    Py_XDECREF(slots_arr); Py_XDECREF(appendix); Py_DECREF(fast);
    return nullptr;
  }
  int32_t* slots = (int32_t*)PyArray_DATA((PyArrayObject*)slots_arr);

  // pass 2 — assign slots: pointer-identity hit (interned repeats), byte
  // hit, or new slot + appendix entry (normalized key object). The
  // appendix append runs BEFORE the slot commits: an append failure (OOM)
  // must not leave a slot the Python source of truth never hears about
  // (the no-mutate-on-failure contract ops/keytable.py assumes — a
  // mutated-but-unreported table would diverge the mirror forever).
  const int64_t n0 = kt->n;  // rollback floor: slots committed this call
  bool fail = false;
  for (npy_intp i = 0; i < n && !fail; i++) {
    PyObject* it = items[i];
    auto pit = kt->ptr_cache.find(it);
    if (pit != kt->ptr_cache.end()) {
      slots[i] = pit->second;
      continue;
    }
    StrKey key{spans[(size_t)i].first, (size_t)spans[(size_t)i].second};
    auto bit = kt->byte_map.find(key);
    int32_t slot;
    if (bit != kt->byte_map.end()) {
      slot = bit->second;
    } else {
      // appendix carries the NORMALIZED key ("" for None, else the raw
      // string object) in first-seen order — feeding exactly this
      // sequence to a Python KeyTable assigns identical ids
      if (it == Py_None) {
        PyObject* empty = PyUnicode_FromStringAndSize("", 0);
        if (empty == nullptr || PyList_Append(appendix, empty) < 0) {
          Py_XDECREF(empty);
          fail = true;
          break;
        }
        Py_DECREF(empty);
      } else if (PyList_Append(appendix, it) < 0) {
        fail = true;
        break;
      }
      slot = (int32_t)kt->n++;
      kt->storage.emplace_back(key.p, key.n);
      const std::string& owned = kt->storage.back();
      kt->byte_map.emplace(StrKey{owned.data(), owned.size()}, slot);
    }
    slots[i] = slot;
    if (kt->ptr_cache.size() < kPtrCacheCap) {
      Py_INCREF(it);
      kt->ptr_cache.emplace(it, slot);
    }
  }
  Py_DECREF(fast);
  if (fail) {
    // mid-batch failure: EARLIER rows of this call may have committed
    // slots whose appendix will now never reach the Python table — roll
    // every slot >= n0 back out of storage/byte_map/n, and evict
    // ptr_cache entries pointing at them (a stale pointer hit would
    // otherwise resurrect a slot id the table no longer assigns)
    while (kt->n > n0) {
      const std::string& owned = kt->storage.back();
      kt->byte_map.erase(StrKey{owned.data(), owned.size()});
      kt->storage.pop_back();
      kt->n--;
    }
    for (auto itc = kt->ptr_cache.begin(); itc != kt->ptr_cache.end();) {
      if (itc->second >= n0) {
        Py_DECREF(itc->first);
        itc = kt->ptr_cache.erase(itc);
      } else {
        ++itc;
      }
    }
    Py_DECREF(slots_arr);
    Py_DECREF(appendix);
    return nullptr;
  }
  PyObject* out = PyTuple_Pack(2, slots_arr, appendix);
  Py_DECREF(slots_arr);
  Py_DECREF(appendix);
  return out;
}

// ---------------------------------------------------------------------------
// Persistent int64 -> slot table (an integer GROUP BY key: an id, a code).
//
// A numeric key column is already a flat buffer, so its dictionary encode
// needs no Python object at all: one pass over the int64 values, open
// addressing (linear probing, load <= 1/2) over two flat arrays. As with
// KeyTab the Python KeyTable stays the source of truth: the caller passes
// the next free slot, misses take dense slots from there on in first-seen
// order and come back as an int64 appendix; keytab_load_i64 mirrors
// (key, slot) pairs the Python paths assigned (catch-up, restore).
//
// Contract: encode/load either complete or raise having changed nothing.
// The pass keeps the interpreter lock (0.5 ms a 32,768-row column; giving
// it up cost the caller ~2.4 ms of queueing to get it back and bought
// nothing end to end, PERF.md PR 39), so callers are serialised as KeyTab's.

struct I64Tab {
  std::vector<int64_t> keys;
  std::vector<int32_t> slots;  // -1 = empty cell
  size_t mask = 0;             // cells - 1 (cells is a power of two)
  size_t count = 0;

  static size_t mix(int64_t k) {  // splitmix64 finalizer
    uint64_t x = (uint64_t)k;
    x ^= x >> 30; x *= 0xbf58476d1ce4e5b9ULL;
    x ^= x >> 27; x *= 0x94d049bb133111ebULL;
    x ^= x >> 31;
    return (size_t)x;
  }

  // the cell holding `k`, or the empty cell where it would go
  size_t probe(int64_t k) const {
    size_t i = mix(k) & mask;
    while (slots[i] >= 0 && keys[i] != k) i = (i + 1) & mask;
    return i;
  }

  // room for `extra` more keys at load <= 1/2; throws std::bad_alloc
  // BEFORE anything moved
  void reserve(size_t extra) {
    size_t cells = mask + 1;
    if (!slots.empty() && (count + extra) * 2 <= cells) return;
    size_t want = slots.empty() ? 1024 : cells;
    while ((count + extra) * 2 > want) want *= 2;
    std::vector<int64_t> nk(want, 0);
    std::vector<int32_t> ns(want, -1);
    const size_t nmask = want - 1;
    for (size_t i = 0; i < slots.size(); i++) {
      if (slots[i] < 0) continue;
      size_t j = mix(keys[i]) & nmask;
      while (ns[j] >= 0) j = (j + 1) & nmask;
      nk[j] = keys[i];
      ns[j] = slots[i];
    }
    keys.swap(nk);
    slots.swap(ns);
    mask = nmask;
  }

  // roll a key of a failed call back out (backward-shift deletion keeps
  // every probe chain whole without tombstones)
  void erase(int64_t k) {
    size_t i = probe(k);
    if (slots[i] < 0) return;
    size_t j = i;
    for (;;) {
      j = (j + 1) & mask;
      if (slots[j] < 0) break;
      size_t home = mix(keys[j]) & mask;
      // cell j may move into the hole at i unless its home lies
      // cyclically in (i, j]
      bool stays = (i <= j) ? (home > i && home <= j)
                            : (home > i || home <= j);
      if (stays) continue;
      keys[i] = keys[j];
      slots[i] = slots[j];
      i = j;
    }
    slots[i] = -1;
    count--;
  }
};

void i64tab_destruct(PyObject* cap) {
  delete (I64Tab*)PyCapsule_GetPointer(cap, "ekjsoncol.keytab_i64");
}

I64Tab* i64tab_of(PyObject* cap) {
  return (I64Tab*)PyCapsule_GetPointer(cap, "ekjsoncol.keytab_i64");
}

// `obj` as a C-contiguous 1-D array of exactly `typenum` (new reference);
// TypeError otherwise — a cast here could alias keys (2.0 -> 2)
PyArrayObject* exact_1d(PyObject* obj, int typenum, const char* what) {
  if (!PyArray_Check(obj) || PyArray_NDIM((PyArrayObject*)obj) != 1 ||
      !PyArray_EquivTypenums(PyArray_TYPE((PyArrayObject*)obj), typenum) ||
      !PyArray_ISBEHAVED_RO((PyArrayObject*)obj)) {
    PyErr_Format(PyExc_TypeError, "%s: expected a 1-D aligned native array",
                 what);
    return nullptr;
  }
  return (PyArrayObject*)PyArray_GETCONTIGUOUS((PyArrayObject*)obj);
}

PyObject* i64_new(PyObject*, PyObject*) {
  return PyCapsule_New(new I64Tab(), "ekjsoncol.keytab_i64",
                       i64tab_destruct);
}

PyObject* i64_encode(PyObject*, PyObject* args) {
  PyObject* cap;
  PyObject* col_obj;
  long long next_slot;
  if (!PyArg_ParseTuple(args, "OOL", &cap, &col_obj, &next_slot))
    return nullptr;
  if (next_slot < 0 || next_slot > INT32_MAX) {
    PyErr_SetString(PyExc_OverflowError, "next_slot outside int32");
    return nullptr;
  }
  PyArrayObject* col = exact_1d(col_obj, NPY_INT64, "keytab_encode_i64");
  if (col == nullptr) return nullptr;
  npy_intp n = PyArray_DIM(col, 0);
  PyObject* slots_arr = PyArray_SimpleNew(1, &n, NPY_INT32);
  I64Tab* t = slots_arr != nullptr ? i64tab_of(cap) : nullptr;
  if (t == nullptr) {
    Py_XDECREF(slots_arr); Py_DECREF(col);
    return nullptr;
  }
  const int64_t* in = (const int64_t*)PyArray_DATA(col);
  int32_t* out = (int32_t*)PyArray_DATA((PyArrayObject*)slots_arr);
  std::vector<int64_t> fresh;  // the appendix: new keys, first seen first
  int fail = 0;  // 1 = out of memory, 2 = slots exhausted, 3 = no appendix

  try {
    t->reserve(1);
    for (npy_intp i = 0; i < n; i++) {
      const int64_t k = in[i];
      const size_t c = t->probe(k);
      int32_t slot = t->slots[c];
      if (slot < 0) {
        const long long next = next_slot + (long long)fresh.size();
        if (next > INT32_MAX) {
          fail = 2;
          break;
        }
        fresh.push_back(k);
        slot = (int32_t)next;
        t->keys[c] = k;
        t->slots[c] = slot;
        t->count++;
        t->reserve(1);  // may move every cell: `c` is dead from here
      }
      out[i] = slot;
    }
  } catch (const std::bad_alloc&) {
    fail = 1;
  }

  PyObject* appendix = nullptr;
  if (!fail) {
    npy_intp m = (npy_intp)fresh.size();
    appendix = PyArray_SimpleNew(1, &m, NPY_INT64);
    if (appendix == nullptr) {
      fail = 3;  // MemoryError is set
    } else if (m > 0) {
      std::memcpy(PyArray_DATA((PyArrayObject*)appendix), fresh.data(),
                  (size_t)m * sizeof(int64_t));
    }
  }
  if (fail) {
    // the Python table will never hear of these slots: take them back
    for (int64_t k : fresh) t->erase(k);
  }
  Py_DECREF(col);
  PyObject* res = nullptr;
  if (fail == 1) {
    PyErr_NoMemory();
  } else if (fail == 2) {
    PyErr_SetString(PyExc_OverflowError, "slot ids exceed int32");
  } else if (appendix != nullptr) {
    res = PyTuple_Pack(2, slots_arr, appendix);
  }
  Py_DECREF(slots_arr);
  Py_XDECREF(appendix);
  return res;
}

PyObject* i64_load(PyObject*, PyObject* args) {
  PyObject* cap;
  PyObject* keys_obj;
  PyObject* slots_obj;
  if (!PyArg_ParseTuple(args, "OOO", &cap, &keys_obj, &slots_obj))
    return nullptr;
  PyArrayObject* ka = exact_1d(keys_obj, NPY_INT64, "keytab_load_i64 keys");
  if (ka == nullptr) return nullptr;
  PyArrayObject* sa = exact_1d(slots_obj, NPY_INT32, "keytab_load_i64 slots");
  if (sa == nullptr) {
    Py_DECREF(ka);
    return nullptr;
  }
  const npy_intp n = PyArray_DIM(ka, 0);
  const int64_t* ks = (const int64_t*)PyArray_DATA(ka);
  const int32_t* ss = (const int32_t*)PyArray_DATA(sa);
  I64Tab* t = nullptr;
  if (PyArray_DIM(sa, 0) != n) {
    PyErr_SetString(PyExc_ValueError, "keys and slots differ in length");
  } else {
    t = i64tab_of(cap);
  }
  if (t == nullptr) {
    Py_DECREF(ka); Py_DECREF(sa);
    return nullptr;
  }
  // a pair is new, or says again what the table holds; anything else (a
  // negative slot, a key under another slot) rolls the call back whole
  const char* err = nullptr;
  std::vector<int64_t> added;
  try {
    t->reserve((size_t)n);
    added.reserve((size_t)n);
  } catch (const std::bad_alloc&) {
    err = "";
  }
  for (npy_intp i = 0; err == nullptr && i < n; i++) {
    const size_t c = t->probe(ks[i]);
    if (t->slots[c] >= 0) {
      if (t->slots[c] != ss[i]) err = "key already holds another slot";
    } else if (ss[i] < 0) {
      err = "negative slot";
    } else {
      t->keys[c] = ks[i];
      t->slots[c] = ss[i];
      t->count++;
      added.push_back(ks[i]);
    }
  }
  if (err != nullptr) {
    for (int64_t k : added) t->erase(k);
  }
  Py_DECREF(ka); Py_DECREF(sa);
  if (err != nullptr) {
    if (*err == '\0') return PyErr_NoMemory();
    PyErr_SetString(PyExc_ValueError, err);
    return nullptr;
  }
  Py_RETURN_NONE;
}

PyMethodDef methods[] = {
    {"decode", jc_decode, METH_VARARGS,
     "decode(payloads, fields, shards=1) -> (columns, valid, bad, "
     "(fields_kept, fields_skipped, bytes))"},
    {"keytab_new", kt_new, METH_NOARGS,
     "keytab_new() -> persistent key-slot table capsule"},
    {"keytab_encode", kt_encode, METH_VARARGS,
     "keytab_encode(tab, keys) -> (slots int32, appendix list)"},
    {"keytab_len", kt_len, METH_VARARGS, "keytab_len(tab) -> int"},
    {"keytab_clear", kt_clear, METH_VARARGS, "keytab_clear(tab)"},
    {"keytab_i64_new", i64_new, METH_NOARGS,
     "keytab_i64_new() -> persistent int64 key-slot table capsule"},
    {"keytab_encode_i64", i64_encode, METH_VARARGS,
     "keytab_encode_i64(tab, keys int64, next_slot) -> (slots int32, "
     "appendix int64): one pass, new keys first seen first"},
    {"keytab_load_i64", i64_load, METH_VARARGS,
     "keytab_load_i64(tab, keys int64, slots int32): mirror known pairs"},
    {nullptr, nullptr, 0, nullptr},
};

PyModuleDef moduledef = {
    PyModuleDef_HEAD_INIT, "ekjsoncol",
    "native columnar JSON decoder", -1, methods,
    nullptr, nullptr, nullptr, nullptr,
};

}  // namespace

PyMODINIT_FUNC PyInit_ekjsoncol(void) {
  import_array();
  PyObject* m = PyModule_Create(&moduledef);
  if (m == nullptr) return nullptr;
  FallbackError = PyErr_NewException("ekjsoncol.Fallback", nullptr, nullptr);
  Py_INCREF(FallbackError);
  PyModule_AddObject(m, "Fallback", FallbackError);
  return m;
}
