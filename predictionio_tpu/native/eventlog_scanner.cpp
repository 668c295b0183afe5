// Native event-log scanner: JSONL segments -> columnar arrays.
//
// Role: the host-side ingest hot path (SURVEY.md §2 'TPU-equivalent mapping':
// the reference's HBase scan -> Spark RDD ingest becomes sharded sequential
// segment reads staged to device).  The reference has no C++ (it rides
// HBase/Spark JVM I/O); this is its TPU-native equivalent: parse+encode at
// memory bandwidth so the TPU is never input-bound.
//
// Contract: segments are written by Event.to_json_line() — compact JSON, one
// object per line.  The parser is a minimal but correct JSON tokenizer: it
// extracts event/entityId/entityType/targetEntityId/eventTime and the FULL
// properties map into sparse per-key columns (discovered schema):
//   kind 0 = number (f64), 1 = bool (0/1 in the num facet),
//   kind 2 = string, 3 = list of strings (string facet, per-key dict;
//   numeric/bool list elements are stringified, nested containers inside
//   lists are dropped), 4 = null, 5 = nested object kept as its raw JSON
//   span — dates stay ISO strings for the Python side.
// A legacy dense `rating` column (NaN-missing) is kept as the ALS fast path.
//
// Threading: the unit of work is a byte range of a segment (a few MB, cut
// at line starts), whatever the number of files.  Three phases:
//   parse  workers pull ranges from one queue; each encodes its lines
//          into the range's own columns against the range's own small
//          dictionaries (first-appearance order inside the range);
//   merge  each dictionary walks the ranges in file order and adds the
//          range's DISTINCT strings to the global one (so global order is
//          first appearance over the whole log), one dictionary a worker;
//   fill   workers rewrite each range's code columns through its remap
//          tables straight into the caller's arrays.
// Nothing is done once an event on one thread, and nothing is allocated
// once an event: keys are matched in place, an escape-free string is a
// pointer into the range's buffer, eventTime in the writer's own shape is
// integer arithmetic, a plain decimal needs no strtod.  A value that does
// not fit a fast path takes the general one (counted: slow_strings,
// slow_times).
//
// C ABI (used from Python via ctypes):
//   scan_new() -> handle
//   scan_add_file(h, path)
//   scan_run(h, n_threads, range_bytes) -> row count or -1 (parse + merge;
//       range_bytes 0 = the default, anything else is for tests)
//   scan_prop_* sizes, scan_prop_bind(h, k, ...) destination arrays
//   scan_fill(h, ...) -> writes every column into the caller's arrays
//   scan_dict_* (blob, offsets) of the merged dictionaries, in place
//   scan_stats(h, out[9])
//   scan_error(h) -> last error message
//   scan_free(h)

#include <atomic>
#include <chrono>
#include <cmath>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <deque>
#include <string>
#include <thread>
#include <vector>

#include <fcntl.h>
#include <sys/stat.h>
#include <unistd.h>

namespace {

// A string value: bytes in the range's buffer, or in a pooled scratch
// string when the value had escapes.  Valid until the line is committed.
struct Str {
  const char* p = nullptr;
  size_t n = 0;
  template <size_t N>
  bool is(const char (&lit)[N]) const {
    return n == N - 1 && memcmp(p, lit, N - 1) == 0;
  }
};

// ---------------------------------------------------------------------- JSON

struct Parser {
  const char* p;
  const char* end;

  void skip_ws() {
    while (p < end && (*p == ' ' || *p == '\t' || *p == '\r' || *p == '\n')) p++;
  }

  bool expect(char c) {
    skip_ws();
    if (p < end && *p == c) { p++; return true; }
    return false;
  }

  // The general path: parse a JSON string, appending the decoded value
  // (out may be null: validate and skip).
  bool parse_string(std::string* out) {
    skip_ws();
    if (p >= end || *p != '"') return false;
    p++;
    while (p < end) {
      char c = *p++;
      if (c == '"') return true;
      if (c == '\\') {
        if (p >= end) break;
        char e = *p++;
        switch (e) {
          case '"': if (out) out->push_back('"'); break;
          case '\\': if (out) out->push_back('\\'); break;
          case '/': if (out) out->push_back('/'); break;
          case 'b': if (out) out->push_back('\b'); break;
          case 'f': if (out) out->push_back('\f'); break;
          case 'n': if (out) out->push_back('\n'); break;
          case 'r': if (out) out->push_back('\r'); break;
          case 't': if (out) out->push_back('\t'); break;
          case 'u': {
            if (end - p < 4) return false;
            unsigned code = 0;
            for (int i = 0; i < 4; i++) {
              char h = *p++;
              code <<= 4;
              if (h >= '0' && h <= '9') code |= h - '0';
              else if (h >= 'a' && h <= 'f') code |= h - 'a' + 10;
              else if (h >= 'A' && h <= 'F') code |= h - 'A' + 10;
              else return false;
            }
            // surrogate pair
            if (code >= 0xD800 && code <= 0xDBFF && end - p >= 6 &&
                p[0] == '\\' && p[1] == 'u') {
              unsigned lo = 0;
              const char* q = p + 2;
              for (int i = 0; i < 4; i++) {
                char h = *q++;
                lo <<= 4;
                if (h >= '0' && h <= '9') lo |= h - '0';
                else if (h >= 'a' && h <= 'f') lo |= h - 'a' + 10;
                else if (h >= 'A' && h <= 'F') lo |= h - 'A' + 10;
                else { lo = 0xFFFFFFFF; break; }
              }
              if (lo >= 0xDC00 && lo <= 0xDFFF) {
                code = 0x10000 + ((code - 0xD800) << 10) + (lo - 0xDC00);
                p += 6;
              }
            }
            if (out) {  // encode UTF-8
              if (code < 0x80) out->push_back((char)code);
              else if (code < 0x800) {
                out->push_back((char)(0xC0 | (code >> 6)));
                out->push_back((char)(0x80 | (code & 0x3F)));
              } else if (code < 0x10000) {
                out->push_back((char)(0xE0 | (code >> 12)));
                out->push_back((char)(0x80 | ((code >> 6) & 0x3F)));
                out->push_back((char)(0x80 | (code & 0x3F)));
              } else {
                out->push_back((char)(0xF0 | (code >> 18)));
                out->push_back((char)(0x80 | ((code >> 12) & 0x3F)));
                out->push_back((char)(0x80 | ((code >> 6) & 0x3F)));
                out->push_back((char)(0x80 | (code & 0x3F)));
              }
            }
            break;
          }
          default: return false;
        }
      } else if (out) {
        out->push_back(c);
      }
    }
    return false;
  }

  bool skip_value();  // forward decl

  bool skip_object() {
    if (!expect('{')) return false;
    skip_ws();
    if (p < end && *p == '}') { p++; return true; }
    while (p < end) {
      if (!parse_string(nullptr)) return false;
      if (!expect(':')) return false;
      if (!skip_value()) return false;
      skip_ws();
      if (p < end && *p == ',') { p++; continue; }
      return expect('}');
    }
    return false;
  }

  bool skip_array() {
    if (!expect('[')) return false;
    skip_ws();
    if (p < end && *p == ']') { p++; return true; }
    while (p < end) {
      if (!skip_value()) return false;
      skip_ws();
      if (p < end && *p == ',') { p++; continue; }
      return expect(']');
    }
    return false;
  }

  // A plain decimal (-?digits[.digits], at most 15 digits, then a JSON
  // delimiter) is an exact integer over an exact power of ten: one IEEE
  // division rounds it as strtod does.  Anything else (exponents, NaN,
  // Infinity, hex, long mantissas) goes to strtod; the range's buffer is
  // NUL-terminated behind its last line.
  bool parse_number(double* out) {
    static const double kPow10[16] = {1e0, 1e1, 1e2, 1e3, 1e4, 1e5, 1e6, 1e7,
                                      1e8, 1e9, 1e10, 1e11, 1e12, 1e13, 1e14,
                                      1e15};
    skip_ws();
    const char* q = p;
    bool neg = q < end && *q == '-';
    if (neg) q++;
    const char* d0 = q;
    uint64_t m = 0;
    while (q < end && *q >= '0' && *q <= '9' && q - d0 < 16)
      m = m * 10 + (uint64_t)(*q++ - '0');
    size_t digits = (size_t)(q - d0), frac = 0;
    if (digits && digits < 15 && q < end && *q == '.') {
      const char* f0 = ++q;
      while (q < end && *q >= '0' && *q <= '9' && q - f0 < 16)
        m = m * 10 + (uint64_t)(*q++ - '0');
      frac = (size_t)(q - f0);
      digits = frac ? digits + frac : 0;
    }
    if (digits && digits <= 15 &&
        (q >= end || *q == ',' || *q == '}' || *q == ']' || *q == ' ' ||
         *q == '\t' || *q == '\r' || *q == '\n')) {
      double v = (double)m;
      if (frac) v /= kPow10[frac];
      if (out) *out = neg ? -v : v;
      p = q;
      return true;
    }
    char* numend = nullptr;
    double v = strtod(p, &numend);
    if (numend == p) return false;
    if (out) *out = v;
    p = numend;
    return true;
  }

  bool skip_literal(const char* lit) {
    size_t n = strlen(lit);
    if ((size_t)(end - p) >= n && strncmp(p, lit, n) == 0) { p += n; return true; }
    return false;
  }
};

bool Parser::skip_value() {
  skip_ws();
  if (p >= end) return false;
  switch (*p) {
    case '"': return parse_string(nullptr);
    case '{': return skip_object();
    case '[': return skip_array();
    case 't': return skip_literal("true");
    case 'f': return skip_literal("false");
    case 'n': return skip_literal("null");
    default: return parse_number(nullptr);
  }
}

// days since epoch for a civil date (Howard Hinnant's algorithm)
int64_t days_from_civil(int y, unsigned m, unsigned d) {
  y -= m <= 2;
  const int era = (y >= 0 ? y : y - 399) / 400;
  const unsigned yoe = (unsigned)(y - era * 400);
  const unsigned doy = (153 * (m + (m > 2 ? -3 : 9)) + 2) / 5 + d - 1;
  const unsigned doe = yoe * 365 + yoe / 4 - yoe / 100 + doy;
  return (int64_t)era * 146097 + (int64_t)doe - 719468;
}

// Both timestamp paths end here, so they round alike (and as Python's
// int(datetime.timestamp() * 1e6) does).
int64_t epoch_us(int y, int mo, int d, int h, int mi, double sec,
                 int64_t offset_s) {
  int64_t days = days_from_civil(y, (unsigned)mo, (unsigned)d);
  double total = (double)days * 86400.0 + h * 3600.0 + mi * 60.0 + sec - (double)offset_s;
  return (int64_t)(total * 1e6);
}

// ISO-8601 -> epoch microseconds, the general path: handles
// "YYYY-MM-DDTHH:MM:SS[.ffffff]" with "Z" or "+HH:MM"/"-HH:MM" offset in
// whatever widths sscanf takes.
bool parse_iso8601_us(const std::string& s, int64_t* out) {
  int y, mo, d, h, mi;
  double sec = 0;
  if (s.size() < 19) return false;
  if (sscanf(s.c_str(), "%d-%d-%dT%d:%d:%lf", &y, &mo, &d, &h, &mi, &sec) != 6)
    return false;
  // find timezone offset after the seconds field
  int64_t offset_s = 0;
  size_t tzpos = s.find_first_of("Z+-", 19);
  // (a '-' inside fractional seconds can't occur; offsets start at/after pos 19)
  if (tzpos != std::string::npos) {
    char c = s[tzpos];
    if (c == '+' || c == '-') {
      int oh = 0, om = 0;
      if (sscanf(s.c_str() + tzpos + 1, "%d:%d", &oh, &om) >= 1) {
        offset_s = (int64_t)oh * 3600 + (int64_t)om * 60;
        if (c == '-') offset_s = -offset_s;
      }
    }
  }
  *out = epoch_us(y, mo, d, h, mi, sec, offset_s);
  return true;
}

inline bool two_digits(const char* s, int* out) {
  unsigned a = (unsigned)(s[0] - '0'), b = (unsigned)(s[1] - '0');
  if (a > 9 || b > 9) return false;
  *out = (int)(a * 10 + b);
  return true;
}

// The shape the store's own writer emits (datetime.isoformat):
// YYYY-MM-DDTHH:MM:SS[.f{1,9}](Z|+HH:MM|-HH:MM|nothing), read by integer
// arithmetic.  False = not that shape (the caller takes the general path).
bool parse_iso8601_fixed(const char* s, size_t n, int64_t* out) {
  int yh, yl, mo, d, h, mi, ss;
  if (n < 19 || s[4] != '-' || s[7] != '-' || s[10] != 'T' || s[13] != ':' ||
      s[16] != ':' || !two_digits(s, &yh) || !two_digits(s + 2, &yl) ||
      !two_digits(s + 5, &mo) || !two_digits(s + 8, &d) ||
      !two_digits(s + 11, &h) || !two_digits(s + 14, &mi) ||
      !two_digits(s + 17, &ss))
    return false;
  size_t i = 19;
  double sec = (double)ss;
  if (i < n && s[i] == '.') {
    // seconds as sscanf's %lf reads "SS.fff": an exact integer over an
    // exact power of ten, one correctly rounded division
    uint64_t num = (uint64_t)ss, den = 1;
    size_t f0 = ++i;
    while (i < n && s[i] >= '0' && s[i] <= '9' && i - f0 < 9) {
      num = num * 10 + (uint64_t)(s[i++] - '0');
      den *= 10;
    }
    if (i == f0) return false;
    sec = (double)num / (double)den;
  }
  int64_t offset_s = 0;
  if (i == n || (s[i] == 'Z' && i + 1 == n)) {
    // UTC (a time with no zone reads as UTC)
  } else if ((s[i] == '+' || s[i] == '-') && i + 6 == n && s[i + 3] == ':') {
    int oh, om;
    if (!two_digits(s + i + 1, &oh) || !two_digits(s + i + 4, &om)) return false;
    offset_s = (int64_t)oh * 3600 + (int64_t)om * 60;
    if (s[i] == '-') offset_s = -offset_s;
  } else {
    return false;
  }
  *out = epoch_us(yh * 100 + yl, mo, d, h, mi, sec, offset_s);
  return true;
}

// -------------------------------------------------------------- dictionaries

inline uint64_t hash_bytes(const char* p, size_t n) {
  const uint64_t k = 0xD6E8FEB86659FD93ull;
  uint64_t h = 0x9E3779B97F4A7C15ull + n;
  while (n >= 8) {
    uint64_t w;
    memcpy(&w, p, 8);
    h = (h ^ w) * k;
    h ^= h >> 32;
    p += 8;
    n -= 8;
  }
  if (n) {
    uint64_t w = 0;
    for (size_t i = 0; i < n; i++) w |= (uint64_t)(unsigned char)p[i] << (8 * i);
    h = (h ^ w) * k;
    h ^= h >> 32;
  }
  h *= 0xBF58476D1CE4E5B9ull;
  return h ^ (h >> 29);
}

// Strings in first-appearance order: the bytes back to back with n+1
// offsets (the export format, handed out in place), and an open-addressing
// index of (hash tag, id+1) words.  A range's and the global dictionaries
// are the same thing at different sizes.
struct Dict {
  std::vector<char> blob;
  std::vector<int64_t> offs{0};
  std::vector<uint64_t> hashes;
  std::vector<uint64_t> slots;
  int32_t last = -1;  // runs of one value (event, entityType) skip the probe

  size_t size() const { return hashes.size(); }
  const char* str(size_t id) const { return blob.data() + offs[id]; }
  size_t len(size_t id) const { return (size_t)(offs[id + 1] - offs[id]); }
  bool equals(size_t id, const char* p, size_t n) const {
    return len(id) == n && memcmp(str(id), p, n) == 0;
  }

  int32_t add(Str s) {
    if (last >= 0 && equals((size_t)last, s.p, s.n)) return last;
    return add_hashed(s.p, s.n, hash_bytes(s.p, s.n));
  }

  int32_t add_hashed(const char* p, size_t n, uint64_t h) {
    if ((hashes.size() + 1) * 2 > slots.size()) grow();
    const size_t mask = slots.size() - 1;
    const uint64_t tag = h >> 32;
    size_t i = (size_t)h & mask;
    for (uint64_t s; (s = slots[i]) != 0; i = (i + 1) & mask) {
      if ((s >> 32) == tag && equals((size_t)(uint32_t)s - 1, p, n))
        return last = (int32_t)((uint32_t)s - 1);
    }
    int32_t id = (int32_t)hashes.size();
    slots[i] = (tag << 32) | (uint32_t)(id + 1);
    hashes.push_back(h);
    blob.insert(blob.end(), p, p + n);
    offs.push_back((int64_t)blob.size());
    return last = id;
  }

  void grow() {
    size_t cap = slots.empty() ? 64 : slots.size() * 2;
    slots.assign(cap, 0);
    for (size_t id = 0; id < hashes.size(); id++) {
      size_t i = (size_t)hashes[id] & (cap - 1);
      while (slots[i]) i = (i + 1) & (cap - 1);
      slots[i] = ((hashes[id] >> 32) << 32) | (uint32_t)(id + 1);
    }
  }
};

// ------------------------------------------------------------ a range's part

// One property key's entries inside one range (local rows, local codes).
struct PropPart {
  std::vector<int32_t> rows;
  std::vector<int8_t> kind;
  std::vector<double> num;
  std::vector<int32_t> n_strs;   // strings an entry
  std::vector<int32_t> codes;
  Dict dict;
  // set by the merge: where the part lands in the global column
  int col = -1;
  int64_t entry_base = 0, code_base = 0;
  std::vector<int32_t> remap;
};

enum { D_EVENT, D_ENTITY_TYPE, D_ENTITY, D_TARGET, N_DICTS };

struct Range {
  size_t file = 0;
  int64_t begin = 0, end = 0;            // lines that START in [begin, end)

  std::vector<int32_t> codes[N_DICTS];   // target: -1 = none
  std::vector<int64_t> time_us;
  std::vector<float> rating;
  Dict dicts[N_DICTS];
  Dict prop_keys;
  std::deque<PropPart> props;            // by local key code
  int64_t slow_strings = 0, slow_times = 0;
  std::string error;

  int64_t row_base = 0;                  // set by the merge
  std::vector<int32_t> remap[N_DICTS];

  size_t rows() const { return time_us.size(); }
};

// One property value of the line being parsed, held until the line is
// known to be good: a line dropped at its last byte must leave nothing in
// the range's dictionaries (their order is the result).
struct StagedProp {
  Str key;
  int8_t kind;
  double num;
  uint32_t s0, s1;   // its strings: strs[s0:s1]
};

// Parses lines into one Range.  Owns the per-line scratch, reused from
// line to line.
struct LineParser {
  Range* out = nullptr;
  std::deque<std::string> pool;   // decoded values of the current line
  size_t pool_used = 0;
  std::vector<StagedProp> staged;
  std::vector<Str> strs;

  std::string& scratch() {
    if (pool_used == pool.size()) pool.emplace_back();
    std::string& s = pool[pool_used++];
    s.clear();
    return s;
  }

  // A string value that is kept.  Escape-free: the bytes where they lie.
  bool read_str(Parser& ps, Str* v) {
    ps.skip_ws();
    if (ps.p >= ps.end || *ps.p != '"') return false;
    const char* s = ps.p + 1;
    const char* q = s;
    while (q < ps.end && *q != '"' && *q != '\\') q++;
    if (q >= ps.end) return false;
    if (*q == '"') {
      *v = Str{s, (size_t)(q - s)};
      ps.p = q + 1;
      return true;
    }
    out->slow_strings++;
    std::string& buf = scratch();
    if (!ps.parse_string(&buf)) return false;
    *v = Str{buf.data(), buf.size()};
    return true;
  }

  // A value of a key the scan drops (creationTime, eventId, ...).
  bool skip_value(Parser& ps) {
    ps.skip_ws();
    if (ps.p < ps.end && *ps.p == '"') {
      const char* s = ps.p + 1;
      const char* q = (const char*)memchr(s, '"', (size_t)(ps.end - s));
      if (!q) return false;
      if (!memchr(s, '\\', (size_t)(q - s))) { ps.p = q + 1; return true; }
      out->slow_strings++;   // escapes are validated as they always were
    }
    return ps.skip_value();
  }

  bool stage_str(Parser& ps) {
    Str v;
    if (!read_str(ps, &v)) return false;
    strs.push_back(v);
    return true;
  }

  void stage_copy(const char* text) {
    std::string& buf = scratch();
    buf.assign(text);
    strs.push_back(Str{buf.data(), buf.size()});
  }

  // Parse one property VALUE (see the kinds at the top): nulls keep kind 4,
  // nested objects keep their raw JSON span as kind 5; only nested
  // containers INSIDE lists are skipped structurally — the line still parses.
  bool parse_prop_value(Parser& ps, StagedProp* pv) {
    ps.skip_ws();
    if (ps.p >= ps.end) return false;
    char c = *ps.p;
    if (c == '"') { pv->kind = 2; return stage_str(ps); }
    if (c == 't') { pv->kind = 1; pv->num = 1.0; return ps.skip_literal("true"); }
    if (c == 'f') { pv->kind = 1; pv->num = 0.0; return ps.skip_literal("false"); }
    if (c == 'n') { pv->kind = 4; return ps.skip_literal("null"); }
    if (c == '{') {
      const char* start = ps.p;
      if (!ps.skip_object()) return false;
      pv->kind = 5;
      strs.push_back(Str{start, (size_t)(ps.p - start)});
      return true;
    }
    if (c == '[') {
      ps.p++;
      pv->kind = 3;
      ps.skip_ws();
      if (ps.p < ps.end && *ps.p == ']') { ps.p++; return true; }
      while (ps.p < ps.end) {
        ps.skip_ws();
        if (ps.p >= ps.end) break;
        char e = *ps.p;
        if (e == '"') {
          if (!stage_str(ps)) return false;
        } else if (e == 't') {
          if (!ps.skip_literal("true")) return false;
          strs.push_back(Str{"true", 4});
        } else if (e == 'f') {
          if (!ps.skip_literal("false")) return false;
          strs.push_back(Str{"false", 5});
        } else if (e == 'n') {
          if (!ps.skip_literal("null")) return false;  // dropped
        } else if (e == '{') {
          if (!ps.skip_object()) return false;         // dropped
        } else if (e == '[') {
          if (!ps.skip_array()) return false;          // dropped
        } else {
          double v;
          if (!ps.parse_number(&v)) return false;
          char buf[32];
          snprintf(buf, sizeof buf, "%.17g", v);
          stage_copy(buf);
        }
        ps.skip_ws();
        if (ps.p < ps.end && *ps.p == ',') { ps.p++; continue; }
        return ps.expect(']');
      }
      return false;
    }
    if (!ps.parse_number(&pv->num)) return false;
    pv->kind = 0;
    return true;
  }

  bool parse_properties(Parser& ps, float* rating) {
    ps.skip_ws();
    if (ps.p >= ps.end || *ps.p != '{') return skip_value(ps);
    ps.p++;
    ps.skip_ws();
    if (ps.p < ps.end && *ps.p == '}') { ps.p++; return true; }
    while (ps.p < ps.end) {
      StagedProp pv{Str{}, -1, NAN, (uint32_t)strs.size(), 0};
      if (!read_str(ps, &pv.key)) return false;
      if (!ps.expect(':')) return false;
      if (!parse_prop_value(ps, &pv)) return false;
      pv.s1 = (uint32_t)strs.size();
      if (pv.kind == 0 && pv.key.is("rating")) *rating = (float)pv.num;
      staged.push_back(pv);
      ps.skip_ws();
      if (ps.p < ps.end && *ps.p == ',') { ps.p++; continue; }
      return ps.expect('}');
    }
    return true;
  }

  // One line -> one row of the range, or nothing.
  bool parse_line(const char* line, const char* line_end) {
    Parser ps{line, line_end};
    pool_used = 0;
    staged.clear();
    strs.clear();
    Str event, entity_type, entity_id, target_id, event_time;
    float rating = NAN;
    if (!ps.expect('{')) return false;
    ps.skip_ws();
    if (ps.p < ps.end && *ps.p == '}') return false;
    while (ps.p < ps.end) {
      Str key;
      if (!read_str(ps, &key)) return false;
      if (!ps.expect(':')) return false;
      bool ok;
      if (key.is("event")) ok = read_str(ps, &event);
      else if (key.is("entityType")) ok = read_str(ps, &entity_type);
      else if (key.is("entityId")) ok = read_str(ps, &entity_id);
      else if (key.is("targetEntityId")) ok = read_str(ps, &target_id);
      else if (key.is("eventTime")) ok = read_str(ps, &event_time);
      else if (key.is("properties")) ok = parse_properties(ps, &rating);
      else ok = skip_value(ps);
      if (!ok) return false;
      ps.skip_ws();
      if (ps.p < ps.end && *ps.p == ',') { ps.p++; continue; }
      if (!ps.expect('}')) return false;
      break;
    }
    if (!event.n || !entity_id.n) return false;
    int64_t time_us = 0;
    if (event_time.n &&
        !parse_iso8601_fixed(event_time.p, event_time.n, &time_us)) {
      out->slow_times++;
      if (!parse_iso8601_us(std::string(event_time.p, event_time.n), &time_us))
        return false;
    }
    commit(event, entity_type, entity_id, target_id, time_us, rating);
    return true;
  }

  void commit(Str event, Str entity_type, Str entity_id, Str target_id,
              int64_t time_us, float rating) {
    Range& r = *out;
    int32_t row = (int32_t)r.rows();
    r.codes[D_EVENT].push_back(r.dicts[D_EVENT].add(event));
    r.codes[D_ENTITY_TYPE].push_back(r.dicts[D_ENTITY_TYPE].add(entity_type));
    r.codes[D_ENTITY].push_back(r.dicts[D_ENTITY].add(entity_id));
    r.codes[D_TARGET].push_back(
        target_id.n ? r.dicts[D_TARGET].add(target_id) : -1);
    r.time_us.push_back(time_us);
    r.rating.push_back(rating);
    for (const StagedProp& pv : staged) {
      size_t k = (size_t)r.prop_keys.add(pv.key);
      if (k == r.props.size()) r.props.emplace_back();
      PropPart& part = r.props[k];
      part.rows.push_back(row);
      part.kind.push_back(pv.kind);
      part.num.push_back(pv.num);
      part.n_strs.push_back((int32_t)(pv.s1 - pv.s0));
      for (uint32_t j = pv.s0; j < pv.s1; j++)
        part.codes.push_back(part.dict.add(strs[j]));
    }
  }
};

// ------------------------------------------------------------------- scanner

// A merged property column.  The arrays are the caller's (scan_prop_bind).
struct PropColumn {
  Dict dict;
  int64_t n_entries = 0, n_codes = 0;
  int64_t* rows = nullptr;
  int8_t* kind = nullptr;
  double* num = nullptr;
  int64_t* str_offs = nullptr;   // n_entries + 1
  int32_t* codes = nullptr;
};

struct Scanner {
  std::vector<std::string> paths;
  std::string error;

  std::vector<int> fds;
  std::vector<int64_t> sizes;
  std::deque<Range> ranges;
  int threads = 0;
  int64_t rows = 0;
  int64_t slow_strings = 0, slow_times = 0;
  double parse_s = 0, merge_s = 0;

  Dict dicts[N_DICTS];
  Dict prop_keys;
  std::deque<PropColumn> prop_cols;   // by global key code

  ~Scanner() { close_files(); }
  void close_files() {
    for (int fd : fds) close(fd);
    fds.clear();
  }
};

const int64_t kRangeBytes = 4 << 20;

// The lines that start in [r.begin, r.end) of r's file, parsed into r.
// Only the file's last line can lack its newline (writer killed
// mid-append): never acknowledged; the Python scan skips it and the
// owning writer truncates it on reopen — surfacing it here would make
// native and Python scans disagree.
void parse_range(Scanner* s, Range& r, LineParser& lp, std::vector<char>& buf) {
  const int fd = s->fds[r.file];
  const int64_t size = s->sizes[r.file];
  // one byte before the range says whether the range starts a line
  const int64_t base = r.begin > 0 ? r.begin - 1 : 0;
  int64_t have = 0;
  auto read_to = [&](int64_t upto) {
    if (upto > size) upto = size;
    buf.resize((size_t)(upto - base) + 1);
    while (base + have < upto) {
      ssize_t got = pread(fd, buf.data() + have, (size_t)(upto - base - have),
                          (off_t)(base + have));
      if (got <= 0) { r.error = "short read on " + s->paths[r.file]; return false; }
      have += got;
    }
    buf[(size_t)have] = '\0';
    return true;
  };
  int64_t slack = 1 << 16;
  if (!read_to(r.end + slack)) return;
  int64_t at = 0;   // offset in buf of the next line
  if (r.begin > 0) {
    const char* nl = (const char*)memchr(buf.data(), '\n', (size_t)(r.end - base));
    if (!nl) return;   // no line starts in this range
    at = nl - buf.data() + 1;
  }
  const size_t guess = (size_t)(r.end - r.begin) / 160 + 16;
  for (auto& c : r.codes) c.reserve(guess);
  r.time_us.reserve(guess);
  r.rating.reserve(guess);
  lp.out = &r;
  while (base + at < r.end) {
    const char* nl;
    while (!(nl = (const char*)memchr(buf.data() + at, '\n', (size_t)(have - at)))) {
      if (base + have >= size) return;   // the torn tail
      slack *= 2;
      if (!read_to(base + have + slack)) return;
    }
    const char* line = buf.data() + at;
    if (nl > line) lp.parse_line(line, nl);
    at = nl - buf.data() + 1;
  }
}

// fn(worker, i) for i in [0, n) on up to `threads` workers (the caller is
// worker 0), one shared queue.
template <class F>
void parallel_for(size_t n, int threads, F fn) {
  std::atomic<size_t> next{0};
  auto worker = [&](size_t w) {
    for (size_t i; (i = next.fetch_add(1)) < n;) fn(w, i);
  };
  std::vector<std::thread> pool;
  for (size_t w = 1; w < std::min<size_t>((size_t)threads, n); w++)
    pool.emplace_back(worker, w);
  worker(0);
  for (auto& t : pool) t.join();
}

double seconds_since(std::chrono::steady_clock::time_point t0) {
  return std::chrono::duration<double>(std::chrono::steady_clock::now() - t0).count();
}

PropPart* part_of(Range& r, int col) {
  for (PropPart& p : r.props) if (p.col == col) return &p;
  return nullptr;
}

// One global dictionary from the ranges' own, in file order: a string's
// first range is the earliest that holds it and local order is kept, so
// the result is first-appearance order over the whole log.  which < N_DICTS
// is one of the four id dictionaries, N_DICTS + k property column k's.
void merge_dict(Scanner* s, size_t which) {
  const int col = (int)which - N_DICTS;
  Dict& global = col < 0 ? s->dicts[which] : s->prop_cols[(size_t)col].dict;
  for (Range& r : s->ranges) {
    PropPart* part = col < 0 ? nullptr : part_of(r, col);
    if (col >= 0 && !part) continue;
    const Dict& local = part ? part->dict : r.dicts[which];
    std::vector<int32_t>& remap = part ? part->remap : r.remap[which];
    remap.resize(local.size());
    for (size_t id = 0; id < local.size(); id++)
      remap[id] = global.add_hashed(local.str(id), local.len(id), local.hashes[id]);
  }
}

}  // namespace

extern "C" {

void* scan_new() { return new Scanner(); }

void scan_free(void* h) { delete (Scanner*)h; }

void scan_add_file(void* h, const char* path) {
  ((Scanner*)h)->paths.emplace_back(path);
}

const char* scan_error(void* h) { return ((Scanner*)h)->error.c_str(); }

// Parse every range and merge the dictionaries.  Returns row count, or -1
// on error.
int64_t scan_run(void* h, int n_threads, int64_t range_bytes) {
  Scanner* s = (Scanner*)h;
  if (range_bytes <= 0) range_bytes = kRangeBytes;
  for (size_t f = 0; f < s->paths.size(); f++) {
    int fd = open(s->paths[f].c_str(), O_RDONLY | O_CLOEXEC);
    struct stat st;
    if (fd < 0 || fstat(fd, &st) != 0) {
      if (fd >= 0) close(fd);
      s->error = "cannot open " + s->paths[f];
      return -1;
    }
    s->fds.push_back(fd);
    s->sizes.push_back((int64_t)st.st_size);
    for (int64_t at = 0; at < st.st_size; at += range_bytes) {
      s->ranges.emplace_back();
      Range& r = s->ranges.back();
      r.file = f;
      r.begin = at;
      r.end = std::min<int64_t>(at + range_bytes, st.st_size);
    }
  }
  s->threads = (int)std::min<size_t>((size_t)std::max(n_threads, 1),
                                     std::max<size_t>(s->ranges.size(), 1));

  auto t0 = std::chrono::steady_clock::now();
  {
    // a worker's line scratch and read buffer, reused from range to range
    std::vector<LineParser> parsers((size_t)s->threads);
    std::vector<std::vector<char>> bufs((size_t)s->threads);
    parallel_for(s->ranges.size(), s->threads, [&](size_t w, size_t i) {
      parse_range(s, s->ranges[i], parsers[w], bufs[w]);
    });
  }
  s->close_files();
  s->parse_s = seconds_since(t0);

  t0 = std::chrono::steady_clock::now();
  // rows and property columns: where each range's part lands
  for (Range& r : s->ranges) {
    if (!r.error.empty()) { s->error = r.error; return -1; }
    s->slow_strings += r.slow_strings;
    s->slow_times += r.slow_times;
    r.row_base = s->rows;
    s->rows += (int64_t)r.rows();
    for (size_t k = 0; k < r.props.size(); k++) {
      PropPart& part = r.props[k];
      part.col = s->prop_keys.add(Str{r.prop_keys.str(k), r.prop_keys.len(k)});
      if ((size_t)part.col == s->prop_cols.size()) s->prop_cols.emplace_back();
      PropColumn& col = s->prop_cols[(size_t)part.col];
      part.entry_base = col.n_entries;
      part.code_base = col.n_codes;
      col.n_entries += (int64_t)part.rows.size();
      col.n_codes += (int64_t)part.codes.size();
    }
  }
  // the dictionaries are independent of each other: one a worker
  parallel_for(N_DICTS + s->prop_cols.size(), s->threads,
               [s](size_t, size_t which) { merge_dict(s, which); });
  s->merge_s = seconds_since(t0);
  return s->rows;
}

// Write every column into the caller's arrays (the six here, and each
// property column's through scan_prop_bind), codes rewritten from the
// ranges' to the merged dictionaries.  Frees the ranges.
void scan_fill(void* h, int32_t* event, int32_t* entity_type, int32_t* entity,
               int32_t* target, int64_t* time_us, float* rating) {
  Scanner* s = (Scanner*)h;
  auto t0 = std::chrono::steady_clock::now();
  int32_t* dst[N_DICTS] = {event, entity_type, entity, target};
  parallel_for(s->ranges.size(), s->threads, [&](size_t, size_t i) {
    Range& r = s->ranges[i];
    const size_t n = r.rows();
    for (int d = 0; d < N_DICTS; d++) {
      const int32_t* remap = r.remap[d].data();
      const int32_t* src = r.codes[d].data();
      int32_t* out = dst[d] + r.row_base;
      for (size_t j = 0; j < n; j++) out[j] = src[j] < 0 ? -1 : remap[src[j]];
    }
    if (n) {
      memcpy(time_us + r.row_base, r.time_us.data(), n * sizeof(int64_t));
      memcpy(rating + r.row_base, r.rating.data(), n * sizeof(float));
    }
    for (PropPart& part : r.props) {
      PropColumn& col = s->prop_cols[(size_t)part.col];
      const size_t m = part.rows.size();
      int64_t at = part.code_base;
      for (size_t j = 0; j < m; j++) {
        col.rows[part.entry_base + (int64_t)j] = r.row_base + part.rows[j];
        col.str_offs[part.entry_base + (int64_t)j] = at;
        at += part.n_strs[j];
      }
      memcpy(col.kind + part.entry_base, part.kind.data(), m * sizeof(int8_t));
      memcpy(col.num + part.entry_base, part.num.data(), m * sizeof(double));
      for (size_t j = 0; j < part.codes.size(); j++)
        col.codes[part.code_base + (int64_t)j] = part.remap[(size_t)part.codes[j]];
    }
    r = Range();
  });
  for (PropColumn& col : s->prop_cols) col.str_offs[col.n_entries] = col.n_codes;
  s->merge_s += seconds_since(t0);
}

// files, bytes, ranges, threads, rows, parse_s, merge_s, slow_strings,
// slow_times.
void scan_stats(void* h, double* out) {
  Scanner* s = (Scanner*)h;
  int64_t bytes = 0;
  for (int64_t b : s->sizes) bytes += b;
  out[0] = (double)s->paths.size();
  out[1] = (double)bytes;
  out[2] = (double)s->ranges.size();
  out[3] = (double)s->threads;
  out[4] = (double)s->rows;
  out[5] = s->parse_s;
  out[6] = s->merge_s;
  out[7] = (double)s->slow_strings;
  out[8] = (double)s->slow_times;
}

// which: 0 event, 1 entityType, 2 entityId, 3 targetEntityId, 4 the
// property keys, 5 + k the strings of property column k.
static Dict* dict_by_id(Scanner* s, int which) {
  if (which < 0) return nullptr;
  if (which < N_DICTS) return &s->dicts[which];
  if (which == N_DICTS) return &s->prop_keys;
  size_t k = (size_t)(which - N_DICTS - 1);
  return k < s->prop_cols.size() ? &s->prop_cols[k].dict : nullptr;
}

int64_t scan_dict_size(void* h, int which) {
  Dict* d = dict_by_id((Scanner*)h, which);
  return d ? (int64_t)d->size() : -1;
}

// A dictionary as (blob, offsets[n+1]), in place.  Length-delimited (NOT
// c_str): JSON keys and values may contain embedded NULs via the \u0000
// escape, and truncation could silently collide two distinct columns on
// the Python side.
const char* scan_dict_blob(void* h, int which) {
  Dict* d = dict_by_id((Scanner*)h, which);
  return d ? d->blob.data() : nullptr;
}

const int64_t* scan_dict_offsets(void* h, int which) {
  Dict* d = dict_by_id((Scanner*)h, which);
  return d ? d->offs.data() : nullptr;
}

// ------------------------------ sparse property columns (discovered schema)

int64_t scan_prop_count(void* h) { return (int64_t)((Scanner*)h)->prop_cols.size(); }

static PropColumn* prop_by_id(void* h, int k) {
  Scanner* s = (Scanner*)h;
  if (k < 0 || (size_t)k >= s->prop_cols.size()) return nullptr;
  return &s->prop_cols[(size_t)k];
}

int64_t scan_prop_len(void* h, int k) {
  PropColumn* c = prop_by_id(h, k);
  return c ? c->n_entries : -1;
}

int64_t scan_prop_codes_len(void* h, int k) {
  PropColumn* c = prop_by_id(h, k);
  return c ? c->n_codes : -1;
}

// The arrays scan_fill writes column k into: rows, kind, num [n],
// str_offs [n + 1], codes [scan_prop_codes_len].
void scan_prop_bind(void* h, int k, int64_t* rows, int8_t* kind, double* num,
                    int64_t* str_offs, int32_t* codes) {
  PropColumn* c = prop_by_id(h, k);
  if (!c) return;
  c->rows = rows;
  c->kind = kind;
  c->num = num;
  c->str_offs = str_offs;
  c->codes = codes;
}

// --------------------------------------------- chunked COO layout (training)
//
// The device CCO path wants (user, item) pairs grouped into fixed-size user
// chunks, padded to a common width (ops/cco._stage_chunked).  numpy does
// argsort + fancy-indexing + a Python fill loop; this is the O(n) two-pass
// counting layout — at 1B events the layout IS the host pipeline, so it
// lives next to the scanner.
//
//   layout_width(user, n, chunk, n_chunks, pad_multiple) -> padded width
//   layout_fill(user, item, n, chunk, n_chunks, width,
//               out_lu, out_it, out_cnt) -> 0 on success
//
// out_lu/out_it are [n_chunks * width] int32 (caller-zeroed), out_cnt is
// [n_chunks] int32.

int64_t layout_width(const int32_t* user, int64_t n, int32_t chunk,
                     int32_t n_chunks, int32_t pad_multiple) {
  if (chunk <= 0 || n_chunks <= 0) return -1;
  std::vector<int64_t> counts(n_chunks, 0);
  for (int64_t i = 0; i < n; i++) {
    int32_t u = user[i];
    int32_t b = u / chunk;
    // explicit u < 0: truncating division maps [-(chunk-1), -1] to b == 0
    if (u < 0 || b >= n_chunks) return -1;  // user id out of range
    counts[b]++;
  }
  int64_t width = 1;
  for (int64_t c : counts) width = c > width ? c : width;
  if (pad_multiple > 1) width = (width + pad_multiple - 1) / pad_multiple * pad_multiple;
  return width;
}

int32_t layout_fill(const int32_t* user, const int32_t* item, int64_t n,
                    int32_t chunk, int32_t n_chunks, int64_t width,
                    int32_t* out_lu, int32_t* out_it, int32_t* out_cnt) {
  if (chunk <= 0 || n_chunks <= 0 || width <= 0) return -1;
  std::vector<int64_t> cursor(n_chunks, 0);
  for (int64_t i = 0; i < n; i++) {
    int32_t u = user[i];
    int32_t b = u / chunk;
    if (u < 0 || b >= n_chunks) return -1;
    int64_t pos = (int64_t)b * width + cursor[b];
    if (cursor[b] >= width) return -2;  // width too small for this chunk
    out_lu[pos] = u % chunk;
    out_it[pos] = item[i];
    cursor[b]++;
  }
  for (int32_t b = 0; b < n_chunks; b++) out_cnt[b] = (int32_t)cursor[b];
  return 0;
}

}  // extern "C"
