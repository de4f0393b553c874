// qcodec: native byte-stream codec for activation/weight transport.
//
// TPU-native replacement for the reference's pip-native compression stack
// (lz4.frame + zfpy C bindings wrapped per tensor at every socket hop,
// /root/reference/src/dispatcher.py:92-98, src/node.py:122-125). On TPU,
// intra-pod hops ride ICI and need no codec; this library serves the
// host/DCN boundary: an LZ77 byte compressor (LZ4-block-style format of our
// own design) applied after optional quantization done in numpy/JAX.
//
// Format (per block):
//   [u32 raw_len][compressed bytes...]
// Compressed stream: sequences of
//   token: hi 4 bits = literal run len (15 => extended bytes), lo 4 bits =
//   match len - 4 (15 => extended bytes); literals; u16 LE match offset.
// A final sequence may have no match (offset omitted when the stream ends
// after literals).
//
// Exposed via ctypes: see adapt_tpu/comm/native.py.

#include <cstdint>
#include <cstring>
#include <cstddef>

namespace {

constexpr int kMinMatch = 4;
constexpr int kHashBits = 16;
constexpr int kHashSize = 1 << kHashBits;

inline uint32_t hash4(const uint8_t* p) {
  uint32_t v;
  std::memcpy(&v, p, 4);
  return (v * 2654435761u) >> (32 - kHashBits);
}

inline void write_u32(uint8_t* p, uint32_t v) { std::memcpy(p, &v, 4); }
inline uint32_t read_u32(const uint8_t* p) {
  uint32_t v;
  std::memcpy(&v, p, 4);
  return v;
}

// Write a length using the 4-bit base + 255-extension scheme.
inline size_t write_len_ext(uint8_t* dst, size_t pos, size_t len) {
  while (len >= 255) {
    dst[pos++] = 255;
    len -= 255;
  }
  dst[pos++] = static_cast<uint8_t>(len);
  return pos;
}

}  // namespace

extern "C" {

// Worst-case compressed size for n input bytes.
size_t qz_bound(size_t n) { return n + n / 255 + 64; }

// Compress src[0..n) into dst (capacity >= qz_bound(n)).
// Returns compressed size, or 0 on failure.
size_t qz_compress(const uint8_t* src, size_t n, uint8_t* dst,
                   size_t dst_cap) {
  if (dst_cap < qz_bound(n)) return 0;
  size_t out = 0;
  write_u32(dst + out, static_cast<uint32_t>(n));
  out += 4;
  if (n < 16) {  // tiny input: all literals
    size_t tok = out++;
    dst[tok] = 0;
    size_t lit = n;
    if (lit >= 15) {
      dst[tok] = 15 << 4;
      out = write_len_ext(dst, out, lit - 15);
    } else {
      dst[tok] = static_cast<uint8_t>(lit << 4);
    }
    std::memcpy(dst + out, src, lit);
    out += lit;
    return out;
  }

  uint32_t table[kHashSize];
  std::memset(table, 0xFF, sizeof(table));

  size_t anchor = 0;
  size_t ip = 0;
  const size_t mflimit = n - 12;  // stop matching near the end

  while (ip < mflimit) {
    uint32_t h = hash4(src + ip);
    uint32_t ref = table[h];
    table[h] = static_cast<uint32_t>(ip);
    bool match = ref != 0xFFFFFFFFu && ip - ref <= 0xFFFF &&
                 std::memcmp(src + ref, src + ip, kMinMatch) == 0;
    if (!match) {
      ++ip;
      continue;
    }
    // Extend the match forward.
    size_t mlen = kMinMatch;
    while (ip + mlen < n - 5 && src[ref + mlen] == src[ip + mlen]) ++mlen;

    size_t lit = ip - anchor;
    size_t tok = out++;
    uint8_t t = 0;
    if (lit >= 15) {
      t |= 15 << 4;
      out = write_len_ext(dst, out, lit - 15);
    } else {
      t |= static_cast<uint8_t>(lit << 4);
    }
    std::memcpy(dst + out, src + anchor, lit);
    out += lit;
    size_t mcode = mlen - kMinMatch;
    if (mcode >= 15) {
      t |= 15;
      dst[tok] = t;
      out = write_len_ext(dst, out, mcode - 15);
    } else {
      t |= static_cast<uint8_t>(mcode);
      dst[tok] = t;
    }
    uint16_t off = static_cast<uint16_t>(ip - ref);
    std::memcpy(dst + out, &off, 2);
    out += 2;
    ip += mlen;
    anchor = ip;
  }

  // Trailing literals.
  size_t lit = n - anchor;
  size_t tok = out++;
  if (lit >= 15) {
    dst[tok] = 15 << 4;
    out = write_len_ext(dst, out, lit - 15);
  } else {
    dst[tok] = static_cast<uint8_t>(lit << 4);
  }
  std::memcpy(dst + out, src + anchor, lit);
  out += lit;
  return out;
}

// Decompress src[0..n) into dst (capacity dst_cap). Returns decompressed
// size, or 0 on malformed input / capacity overflow.
size_t qz_decompress(const uint8_t* src, size_t n, uint8_t* dst,
                     size_t dst_cap) {
  if (n < 4) return 0;
  size_t raw = read_u32(src);
  if (raw > dst_cap) return 0;
  size_t ip = 4;
  size_t out = 0;
  while (ip < n) {
    uint8_t tok = src[ip++];
    size_t lit = tok >> 4;
    if (lit == 15) {
      while (ip < n && src[ip] == 255) {
        lit += 255;
        ++ip;
      }
      if (ip >= n) return 0;
      lit += src[ip++];
    }
    if (ip + lit > n || out + lit > dst_cap) return 0;
    std::memcpy(dst + out, src + ip, lit);
    ip += lit;
    out += lit;
    if (ip >= n) break;  // stream may end after literals
    size_t mcode = tok & 0x0F;
    if (mcode == 15) {
      while (ip < n && src[ip] == 255) {
        mcode += 255;
        ++ip;
      }
      if (ip >= n) return 0;
      mcode += src[ip++];
    }
    size_t mlen = mcode + kMinMatch;
    if (ip + 2 > n) return 0;
    uint16_t off;
    std::memcpy(&off, src + ip, 2);
    ip += 2;
    if (off == 0 || off > out || out + mlen > dst_cap) return 0;
    // Byte-by-byte copy: offsets < mlen overlap (run encoding).
    const uint8_t* from = dst + out - off;
    for (size_t i = 0; i < mlen; ++i) dst[out + i] = from[i];
    out += mlen;
  }
  return out == raw ? out : 0;
}

}  // extern "C"
