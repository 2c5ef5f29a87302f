// rio-tpu native wire codec.
//
// Behind a plain-C ABI (consumed from Python via ctypes): encoders/decoders
// for the framework's envelope types (RequestEnvelope / ResponseEnvelope /
// Subscription{Request,Response}) in the exact positional-msgpack layout of
// rio_tpu/codec.py + rio_tpu/protocol.py, plus an incremental
// length-delimited frame reader. The reference implements this layer with
// tokio's LengthDelimitedCodec + bincode (rio-rs/src/service.rs:370-378,
// client/mod.rs:199-203). The served path runs the Python codec; this one is
// the independent implementation tests/test_native.py holds it to, byte for
// byte.
//
// No Python.h dependency: the library is pure C++.

#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <deque>
#include <utility>
#include <vector>

namespace {

constexpr size_t kMaxFrame = 8u * 1024u * 1024u;  // codec.py MAX_FRAME

// ---------------------------------------------------------------------------
// msgpack writer (the subset the protocol uses)
// ---------------------------------------------------------------------------

struct Writer {
  std::vector<uint8_t> buf;

  void u8(uint8_t v) { buf.push_back(v); }
  void raw(const uint8_t* p, size_t n) { buf.insert(buf.end(), p, p + n); }
  void be16(uint16_t v) {
    u8(static_cast<uint8_t>(v >> 8));
    u8(static_cast<uint8_t>(v));
  }
  void be32(uint32_t v) {
    u8(static_cast<uint8_t>(v >> 24));
    u8(static_cast<uint8_t>(v >> 16));
    u8(static_cast<uint8_t>(v >> 8));
    u8(static_cast<uint8_t>(v));
  }
  void fixarray(uint8_t n) { u8(0x90 | n); }  // n < 16 throughout the protocol
  void boolean(bool v) { u8(v ? 0xc3 : 0xc2); }
  void uint(uint64_t v) {
    if (v < 0x80) {
      u8(static_cast<uint8_t>(v));
    } else if (v <= 0xff) {
      u8(0xcc);
      u8(static_cast<uint8_t>(v));
    } else if (v <= 0xffff) {
      u8(0xcd);
      be16(static_cast<uint16_t>(v));
    } else if (v <= 0xffffffffull) {
      u8(0xce);
      be32(static_cast<uint32_t>(v));
    } else {
      u8(0xcf);
      for (int i = 7; i >= 0; --i) u8(static_cast<uint8_t>(v >> (8 * i)));
    }
  }
  void str(const uint8_t* p, uint32_t n) {
    if (n < 32) {
      u8(0xa0 | static_cast<uint8_t>(n));
    } else if (n <= 0xff) {
      u8(0xd9);
      u8(static_cast<uint8_t>(n));
    } else if (n <= 0xffff) {
      u8(0xda);
      be16(static_cast<uint16_t>(n));
    } else {
      u8(0xdb);
      be32(n);
    }
    raw(p, n);
  }
  void bin(const uint8_t* p, uint32_t n) {
    if (n <= 0xff) {
      u8(0xc4);
      u8(static_cast<uint8_t>(n));
    } else if (n <= 0xffff) {
      u8(0xc5);
      be16(static_cast<uint16_t>(n));
    } else {
      u8(0xc6);
      be32(n);
    }
    raw(p, n);
  }
};

// Wrap the writer's body in a 4-byte big-endian length prefix; malloc'd so
// Python frees with rn_free.
uint8_t* finish_frame(const Writer& w, uint32_t* out_len) {
  size_t body = w.buf.size();
  if (body > kMaxFrame) return nullptr;
  auto* out = static_cast<uint8_t*>(std::malloc(body + 4));
  if (!out) return nullptr;
  out[0] = static_cast<uint8_t>(body >> 24);
  out[1] = static_cast<uint8_t>(body >> 16);
  out[2] = static_cast<uint8_t>(body >> 8);
  out[3] = static_cast<uint8_t>(body);
  std::memcpy(out + 4, w.buf.data(), body);
  *out_len = static_cast<uint32_t>(body + 4);
  return out;
}

// ---------------------------------------------------------------------------
// msgpack parser (zero-copy: string/bin results are spans into the input)
// ---------------------------------------------------------------------------

struct Parser {
  const uint8_t* base;
  const uint8_t* p;
  const uint8_t* end;

  explicit Parser(const uint8_t* buf, size_t len)
      : base(buf), p(buf), end(buf + len) {}

  bool need(size_t n) const { return static_cast<size_t>(end - p) >= n; }
  uint64_t be(int n) {
    uint64_t v = 0;
    for (int i = 0; i < n; ++i) v = (v << 8) | p[i];
    p += n;
    return v;
  }
  // Returns element count, or -1 on malformed input.
  int array_header() {
    if (!need(1)) return -1;
    uint8_t t = *p++;
    if ((t & 0xf0) == 0x90) return t & 0x0f;
    if (t == 0xdc) return need(2) ? static_cast<int>(be(2)) : -1;
    if (t == 0xdd) return need(4) ? static_cast<int>(be(4)) : -1;
    return -1;
  }
  // Accepts str*, bin*, or nil (as an empty span) — the Python codec packs
  // text fields as str and payloads as bin, but be liberal on input.
  bool str_or_bin(uint32_t* off, uint32_t* len) {
    if (!need(1)) return false;
    uint8_t t = *p++;
    uint64_t n;
    if ((t & 0xe0) == 0xa0) {
      n = t & 0x1f;
    } else if (t == 0xd9 || t == 0xc4) {
      if (!need(1)) return false;
      n = be(1);
    } else if (t == 0xda || t == 0xc5) {
      if (!need(2)) return false;
      n = be(2);
    } else if (t == 0xdb || t == 0xc6) {
      if (!need(4)) return false;
      n = be(4);
    } else if (t == 0xc0) {  // nil → empty (ResponseEnvelope body=None)
      *off = static_cast<uint32_t>(p - base);
      *len = 0;
      return true;
    } else {
      return false;
    }
    if (!need(n)) return false;
    *off = static_cast<uint32_t>(p - base);
    *len = static_cast<uint32_t>(n);
    p += n;
    return true;
  }
  bool uint_(uint64_t* out) {
    if (!need(1)) return false;
    uint8_t t = *p++;
    if (t < 0x80) {
      *out = t;
      return true;
    }
    if (t == 0xcc) {
      if (!need(1)) return false;
      *out = be(1);
      return true;
    }
    if (t == 0xcd) {
      if (!need(2)) return false;
      *out = be(2);
      return true;
    }
    if (t == 0xce) {
      if (!need(4)) return false;
      *out = be(4);
      return true;
    }
    if (t == 0xcf) {
      if (!need(8)) return false;
      *out = be(8);
      return true;
    }
    return false;
  }
  bool boolean(bool* out) {
    if (!need(1)) return false;
    uint8_t t = *p++;
    if (t == 0xc2) {
      *out = false;
      return true;
    }
    if (t == 0xc3) {
      *out = true;
      return true;
    }
    return false;
  }
};

// [false, [kind, detail, payload]] error arm shared by ResponseEnvelope and
// SubscriptionResponse. Fills kind + offs/lens[0]=detail, [1]=payload.
// The kind value is an opaque uint here — new Python-side ErrorKind members
// (e.g. 8 = SERVER_BUSY, the retryable overload shed) need no C++ change,
// only a byte-parity case in tests/test_native.py.
bool parse_error_arm(Parser& pr, uint32_t* kind, uint32_t* offs, uint32_t* lens) {
  if (pr.array_header() != 3) return false;
  uint64_t k;
  if (!pr.uint_(&k)) return false;
  *kind = static_cast<uint32_t>(k);
  if (!pr.str_or_bin(&offs[0], &lens[0])) return false;
  if (!pr.str_or_bin(&offs[1], &lens[1])) return false;
  return true;
}

// Shared length-prefix extraction: pulls every complete frame out of buf
// (compacting it), invoking on_frame(ptr, len) per frame. Returns false when
// an oversized frame poisons the stream.
template <typename F>
bool extract_frames(std::vector<uint8_t>& buf, F&& on_frame) {
  size_t scan = 0;
  bool ok = true;
  while (buf.size() - scan >= 4) {
    const uint8_t* h = buf.data() + scan;
    size_t n = (size_t(h[0]) << 24) | (size_t(h[1]) << 16) |
               (size_t(h[2]) << 8) | size_t(h[3]);
    if (n > kMaxFrame) {
      ok = false;
      break;
    }
    if (buf.size() - scan < 4 + n) break;
    on_frame(h + 4, n);
    scan += 4 + n;
  }
  if (scan > 0) buf.erase(buf.begin(), buf.begin() + static_cast<long>(scan));
  return ok;
}

}  // namespace

extern "C" {

void rn_free(uint8_t* ptr) { std::free(ptr); }

// --- envelope encoders (all return a malloc'd complete frame: 4-byte BE
//     length prefix + payload; caller frees with rn_free) -------------------

// Frame payload = 0x00 kind byte + msgpack [handler_type, handler_id,
// message_type, payload]  (protocol.py encode_request_frame).
uint8_t* rn_encode_request_frame(const uint8_t* ht, uint32_t htl,
                                 const uint8_t* hid, uint32_t hidl,
                                 const uint8_t* mt, uint32_t mtl,
                                 const uint8_t* pay, uint32_t pl,
                                 uint32_t* out_len) {
  Writer w;
  w.u8(0x00);
  w.fixarray(4);
  w.str(ht, htl);
  w.str(hid, hidl);
  w.str(mt, mtl);
  w.bin(pay, pl);
  return finish_frame(w, out_len);
}

// Traced variant: payload = 0x00 kind byte + msgpack [handler_type,
// handler_id, message_type, payload, [trace_id, span_id, sampled]] — the
// appended wire-safe trace_ctx field (protocol.py RequestEnvelope). The
// untraced encoder above stays byte-identical to the legacy 4-element
// layout; tests/test_native.py pins parity for both arities.
uint8_t* rn_encode_request_frame_traced(const uint8_t* ht, uint32_t htl,
                                        const uint8_t* hid, uint32_t hidl,
                                        const uint8_t* mt, uint32_t mtl,
                                        const uint8_t* pay, uint32_t pl,
                                        const uint8_t* tid, uint32_t tidl,
                                        const uint8_t* sid, uint32_t sidl,
                                        int32_t sampled, uint32_t* out_len) {
  Writer w;
  w.u8(0x00);
  w.fixarray(5);
  w.str(ht, htl);
  w.str(hid, hidl);
  w.str(mt, mtl);
  w.bin(pay, pl);
  w.fixarray(3);
  w.str(tid, tidl);
  w.str(sid, sidl);
  w.boolean(sampled != 0);
  return finish_frame(w, out_len);
}

// QoS variant: payload = 0x00 kind byte + msgpack [handler_type, handler_id,
// message_type, payload, trace_slot, tenant, priority?, deadline_ms?] — the
// appended QoS classification fields (protocol.py RequestEnvelope, ISSUE 20).
// trace_slot is nil when sampled < 0 (untraced) or the [trace_id, span_id,
// sampled] triple otherwise; trailing default QoS fields are truncated
// exactly like the Python encoder (deadline_ms==0 dropped, then priority==0)
// so both codecs stay byte-identical. Callers with ALL QoS fields default
// use the legacy/traced encoders above instead (those frames must remain
// byte-identical to pre-QoS layouts).
uint8_t* rn_encode_request_frame_qos(const uint8_t* ht, uint32_t htl,
                                     const uint8_t* hid, uint32_t hidl,
                                     const uint8_t* mt, uint32_t mtl,
                                     const uint8_t* pay, uint32_t pl,
                                     const uint8_t* tid, uint32_t tidl,
                                     const uint8_t* sid, uint32_t sidl,
                                     int32_t sampled, const uint8_t* tenant,
                                     uint32_t tenantl, uint64_t priority,
                                     uint64_t deadline_ms, uint32_t* out_len) {
  Writer w;
  w.u8(0x00);
  uint8_t n = 8;
  if (deadline_ms == 0) {
    n = 7;
    if (priority == 0) n = 6;
  }
  w.fixarray(n);
  w.str(ht, htl);
  w.str(hid, hidl);
  w.str(mt, mtl);
  w.bin(pay, pl);
  if (sampled < 0) {
    w.u8(0xc0);  // nil trace slot holds position 4
  } else {
    w.fixarray(3);
    w.str(tid, tidl);
    w.str(sid, sidl);
    w.boolean(sampled != 0);
  }
  w.str(tenant, tenantl);
  if (n >= 7) w.uint(priority);
  if (n >= 8) w.uint(deadline_ms);
  return finish_frame(w, out_len);
}

// Frame payload = 0x01 kind byte + msgpack [handler_type, handler_id].
uint8_t* rn_encode_subscribe_frame(const uint8_t* ht, uint32_t htl,
                                   const uint8_t* hid, uint32_t hidl,
                                   uint32_t* out_len) {
  Writer w;
  w.u8(0x01);
  w.fixarray(2);
  w.str(ht, htl);
  w.str(hid, hidl);
  return finish_frame(w, out_len);
}

// Frame payload = 0x02 kind byte + msgpack [command, subject, payload]
// (protocol.py encode_command_frame — control-plane stream/saga commands).
uint8_t* rn_encode_command_frame(const uint8_t* cmd, uint32_t cmdl,
                                 const uint8_t* subj, uint32_t subjl,
                                 const uint8_t* pay, uint32_t pl,
                                 uint32_t* out_len) {
  Writer w;
  w.u8(0x02);
  w.fixarray(3);
  w.str(cmd, cmdl);
  w.str(subj, subjl);
  w.bin(pay, pl);
  return finish_frame(w, out_len);
}

// Traced variant: 0x02 + msgpack [command, subject, payload,
// [trace_id, span_id, sampled]] — same appended-field rule as requests.
uint8_t* rn_encode_command_frame_traced(const uint8_t* cmd, uint32_t cmdl,
                                        const uint8_t* subj, uint32_t subjl,
                                        const uint8_t* pay, uint32_t pl,
                                        const uint8_t* tid, uint32_t tidl,
                                        const uint8_t* sid, uint32_t sidl,
                                        int32_t sampled, uint32_t* out_len) {
  Writer w;
  w.u8(0x02);
  w.fixarray(4);
  w.str(cmd, cmdl);
  w.str(subj, subjl);
  w.bin(pay, pl);
  w.fixarray(3);
  w.str(tid, tidl);
  w.str(sid, sidl);
  w.boolean(sampled != 0);
  return finish_frame(w, out_len);
}

// ResponseEnvelope ok arm: [true, body].
uint8_t* rn_encode_response_ok_frame(const uint8_t* body, uint32_t blen,
                                     uint32_t* out_len) {
  Writer w;
  w.fixarray(2);
  w.boolean(true);
  w.bin(body, blen);
  return finish_frame(w, out_len);
}

// ResponseEnvelope error arm: [false, [kind, detail, payload]].
uint8_t* rn_encode_response_err_frame(uint32_t kind, const uint8_t* detail,
                                      uint32_t dlen, const uint8_t* pay,
                                      uint32_t plen, uint32_t* out_len) {
  Writer w;
  w.fixarray(2);
  w.boolean(false);
  w.fixarray(3);
  w.uint(kind);
  w.str(detail, dlen);
  w.bin(pay, plen);
  return finish_frame(w, out_len);
}

// SubscriptionResponse ok arm: [true, message_type, body].
uint8_t* rn_encode_subresponse_ok_frame(const uint8_t* mt, uint32_t mtl,
                                        const uint8_t* body, uint32_t blen,
                                        uint32_t* out_len) {
  Writer w;
  w.fixarray(3);
  w.boolean(true);
  w.str(mt, mtl);
  w.bin(body, blen);
  return finish_frame(w, out_len);
}

// SubscriptionResponse error arm: [false, [kind, detail, payload]].
uint8_t* rn_encode_subresponse_err_frame(uint32_t kind, const uint8_t* detail,
                                         uint32_t dlen, const uint8_t* pay,
                                         uint32_t plen, uint32_t* out_len) {
  Writer w;
  w.fixarray(2);
  w.boolean(false);
  w.fixarray(3);
  w.uint(kind);
  w.str(detail, dlen);
  w.bin(pay, plen);
  return finish_frame(w, out_len);
}

// --- inbound decoders (zero-copy: offs/lens index into the input buffer) ---

// Server-side decode of one frame payload (kind byte + body).
// Returns 0 = request (offs/lens[0..3] = handler_type, handler_id,
// message_type, payload; a 5-element frame additionally fills [4] =
// trace_id, [5] = span_id and sets *sampled to 0/1 — *sampled stays -1 on
// the legacy 4-element layout), 1 = subscribe (offs/lens[0..1]),
// 2 = command (offs/lens[0..2] = command, subject, payload; a 4-element
// frame fills the trace triple into [4]/[5]/*sampled like requests),
// -1 = malformed. offs/lens must hold 6 slots.
int rn_decode_inbound(const uint8_t* buf, uint32_t len, uint32_t* offs,
                      uint32_t* lens, int32_t* sampled) {
  if (len == 0) return -1;
  *sampled = -1;
  Parser pr(buf, len);
  uint8_t kind = *pr.p++;
  if (kind == 0x00) {
    int n = pr.array_header();
    if (n != 4 && n != 5) return -1;
    for (int i = 0; i < 4; ++i)
      if (!pr.str_or_bin(&offs[i], &lens[i])) return -1;
    if (n == 5) {
      if (pr.array_header() != 3) return -1;
      if (!pr.str_or_bin(&offs[4], &lens[4])) return -1;
      if (!pr.str_or_bin(&offs[5], &lens[5])) return -1;
      bool s;
      if (!pr.boolean(&s)) return -1;
      *sampled = s ? 1 : 0;
    }
    return 0;
  }
  if (kind == 0x01) {
    if (pr.array_header() != 2) return -1;
    for (int i = 0; i < 2; ++i)
      if (!pr.str_or_bin(&offs[i], &lens[i])) return -1;
    return 1;
  }
  if (kind == 0x02) {
    int n = pr.array_header();
    if (n != 3 && n != 4) return -1;
    for (int i = 0; i < 3; ++i)
      if (!pr.str_or_bin(&offs[i], &lens[i])) return -1;
    if (n == 4) {
      if (pr.array_header() != 3) return -1;
      if (!pr.str_or_bin(&offs[4], &lens[4])) return -1;
      if (!pr.str_or_bin(&offs[5], &lens[5])) return -1;
      bool s;
      if (!pr.boolean(&s)) return -1;
      *sampled = s ? 1 : 0;
    }
    return 2;
  }
  return -1;
}

// QoS-aware server-side decode of one frame payload. Same contract as
// rn_decode_inbound plus the appended QoS fields: requests may carry 4-8
// elements — position 4 is the trace slot (nil OR the [trace_id, span_id,
// sampled] triple; nil leaves *sampled = -1), [6] = tenant (empty when
// absent), qos[0] = priority, qos[1] = deadline_ms (0 when absent).
// offs/lens must hold 7 slots; qos must hold 2.
int rn_decode_inbound_qos(const uint8_t* buf, uint32_t len, uint32_t* offs,
                          uint32_t* lens, int32_t* sampled, uint64_t* qos) {
  if (len == 0) return -1;
  *sampled = -1;
  offs[6] = lens[6] = 0;
  qos[0] = qos[1] = 0;
  Parser pr(buf, len);
  uint8_t kind = *pr.p++;
  if (kind == 0x00) {
    int n = pr.array_header();
    if (n < 4 || n > 8) return -1;
    for (int i = 0; i < 4; ++i)
      if (!pr.str_or_bin(&offs[i], &lens[i])) return -1;
    if (n >= 5) {
      if (pr.need(1) && *pr.p == 0xc0) {
        ++pr.p;  // nil trace slot (QoS-classified but untraced)
      } else {
        if (pr.array_header() != 3) return -1;
        if (!pr.str_or_bin(&offs[4], &lens[4])) return -1;
        if (!pr.str_or_bin(&offs[5], &lens[5])) return -1;
        bool s;
        if (!pr.boolean(&s)) return -1;
        *sampled = s ? 1 : 0;
      }
    }
    if (n >= 6 && !pr.str_or_bin(&offs[6], &lens[6])) return -1;
    if (n >= 7 && !pr.uint_(&qos[0])) return -1;
    if (n >= 8 && !pr.uint_(&qos[1])) return -1;
    return 0;
  }
  // Subscribe/command frames carry no QoS fields; delegate to the legacy
  // decoder so the two paths can never drift.
  return rn_decode_inbound(buf, len, offs, lens, sampled);
}

// Client-side decode of a ResponseEnvelope payload.
// Returns 1 = ok (offs/lens[0] = body), 0 = error (*kind, offs/lens[0] =
// detail, [1] = payload), -1 = malformed.
int rn_decode_response(const uint8_t* buf, uint32_t len, uint32_t* kind,
                       uint32_t* offs, uint32_t* lens) {
  Parser pr(buf, len);
  if (pr.array_header() != 2) return -1;
  bool ok;
  if (!pr.boolean(&ok)) return -1;
  if (ok) {
    if (!pr.str_or_bin(&offs[0], &lens[0])) return -1;
    return 1;
  }
  if (!parse_error_arm(pr, kind, offs, lens)) return -1;
  return 0;
}

// Client-side decode of a SubscriptionResponse payload.
// Returns 1 = ok (offs/lens[0] = message_type, [1] = body), 0 = error
// (*kind, offs/lens[0] = detail, [1] = payload), -1 = malformed.
int rn_decode_subresponse(const uint8_t* buf, uint32_t len, uint32_t* kind,
                          uint32_t* offs, uint32_t* lens) {
  Parser pr(buf, len);
  int n = pr.array_header();
  if (n == 3) {
    bool ok;
    if (!pr.boolean(&ok) || !ok) return -1;
    if (!pr.str_or_bin(&offs[0], &lens[0])) return -1;
    if (!pr.str_or_bin(&offs[1], &lens[1])) return -1;
    return 1;
  }
  if (n == 2) {
    bool ok;
    if (!pr.boolean(&ok) || ok) return -1;
    if (!parse_error_arm(pr, kind, offs, lens)) return -1;
    return 0;
  }
  return -1;
}

// --- incremental frame reader ---------------------------------------------

struct RnReader {
  std::vector<uint8_t> buf;
  std::deque<std::vector<uint8_t>> ready;
  std::vector<uint8_t> current;  // frame handed to Python, kept alive
};

void* rn_reader_new() { return new RnReader(); }
void rn_reader_free(void* r) { delete static_cast<RnReader*>(r); }

// Appends bytes, extracts complete frames. Returns the number of frames now
// queued, or -1 if a frame exceeds the max size (connection is poisoned).
int rn_reader_feed(void* rp, const uint8_t* data, uint32_t len) {
  auto* r = static_cast<RnReader*>(rp);
  r->buf.insert(r->buf.end(), data, data + len);
  if (!extract_frames(r->buf, [&](const uint8_t* p, size_t n) {
        r->ready.emplace_back(p, p + n);
      }))
    return -1;
  return static_cast<int>(r->ready.size());
}

// Pops the next frame; the returned pointer stays valid until the next call
// to rn_reader_next or rn_reader_free. Returns 1, or 0 when empty.
int rn_reader_next(void* rp, const uint8_t** data, uint32_t* len) {
  auto* r = static_cast<RnReader*>(rp);
  if (r->ready.empty()) return 0;
  r->current = std::move(r->ready.front());
  r->ready.pop_front();
  *data = r->current.data();
  *len = static_cast<uint32_t>(r->current.size());
  return 1;
}

}  // extern "C"
