#include "src/common/GrpcClient.h"

#include <fcntl.h>
#include <netdb.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <sys/socket.h>
#include <sys/time.h>
#include <unistd.h>

#include <algorithm>
#include <cctype>
#include <chrono>
#include <cstring>

#include "src/common/Defs.h"

namespace dynotpu {

namespace {

constexpr uint8_t kFrameData = 0x0;
constexpr uint8_t kFrameHeaders = 0x1;
constexpr uint8_t kFrameRstStream = 0x3;
constexpr uint8_t kFramePushPromise = 0x5;
constexpr uint8_t kFrameSettings = 0x4;
constexpr uint8_t kFramePing = 0x6;
constexpr uint8_t kFrameGoaway = 0x7;
constexpr uint8_t kFrameWindowUpdate = 0x8;
constexpr uint8_t kFrameContinuation = 0x9;

constexpr uint8_t kFlagEndStream = 0x1;
constexpr uint8_t kFlagEndHeaders = 0x4;
constexpr uint8_t kFlagPadded = 0x8;
constexpr uint8_t kFlagPriority = 0x20;
constexpr uint8_t kFlagAck = 0x1;

// absl::StatusCode names for gRPC status numerals, so a failed call reads
// "UNAVAILABLE: runtime rebooting" and not just a number.
const char* grpcStatusName(long code) {
  switch (code) {
    case 0: return "OK";
    case 1: return "CANCELLED";
    case 2: return "UNKNOWN";
    case 3: return "INVALID_ARGUMENT";
    case 4: return "DEADLINE_EXCEEDED";
    case 5: return "NOT_FOUND";
    case 6: return "ALREADY_EXISTS";
    case 7: return "PERMISSION_DENIED";
    case 8: return "RESOURCE_EXHAUSTED";
    case 9: return "FAILED_PRECONDITION";
    case 10: return "ABORTED";
    case 11: return "OUT_OF_RANGE";
    case 12: return "UNIMPLEMENTED";
    case 13: return "INTERNAL";
    case 14: return "UNAVAILABLE";
    case 15: return "DATA_LOSS";
    case 16: return "UNAUTHENTICATED";
    default: return "UNRECOGNIZED_STATUS";
  }
}

// grpc-message values are percent-encoded UTF-8 (gRPC HTTP/2 spec).
std::string percentDecode(std::string_view in) {
  std::string out;
  out.reserve(in.size());
  for (size_t i = 0; i < in.size(); ++i) {
    if (in[i] == '%' && i + 2 < in.size() &&
        std::isxdigit(static_cast<unsigned char>(in[i + 1])) &&
        std::isxdigit(static_cast<unsigned char>(in[i + 2]))) {
      out.push_back(static_cast<char>(
          std::stoi(std::string(in.substr(i + 1, 2)), nullptr, 16)));
      i += 2;
    } else {
      out.push_back(in[i]);
    }
  }
  return out;
}

constexpr const char kPreface[] = "PRI * HTTP/2.0\r\n\r\nSM\r\n\r\n";

// Shared cancel-aware wait: polls `fd` for `events` in 100ms slices until
// readiness, cancellation, deadline, or a poll error. One implementation
// for both the connect handshake and the response-frame wait so the
// EINTR/deadline handling can never drift apart. Returns:
enum class WaitResult { kReady, kCancelled, kDeadline, kError };
WaitResult pollWithCancel(
    int fd,
    short events,
    std::chrono::steady_clock::time_point deadline,
    const std::atomic<bool>* cancel) {
  while (true) {
    if (cancel && cancel->load()) {
      return WaitResult::kCancelled;
    }
    auto left = std::chrono::duration_cast<std::chrono::milliseconds>(
                    deadline - std::chrono::steady_clock::now())
                    .count();
    if (left <= 0) {
      return WaitResult::kDeadline;
    }
    struct pollfd pfd{fd, events, 0};
    int pr = ::poll(&pfd, 1, static_cast<int>(std::min<long long>(left, 100)));
    if (pr > 0) {
      return WaitResult::kReady;
    }
    if (pr < 0 && errno != EINTR) {
      return WaitResult::kError;
    }
  }
}

void putU32(std::string& out, uint32_t v) {
  out.push_back(static_cast<char>(v >> 24));
  out.push_back(static_cast<char>(v >> 16));
  out.push_back(static_cast<char>(v >> 8));
  out.push_back(static_cast<char>(v));
}

// HPACK literal header field, never-indexed, new name (RFC 7541 §6.2.3),
// raw (non-Huffman) strings. Needs no table state on either side.
void hpackLiteral(std::string& out, std::string_view name,
                  std::string_view value) {
  out.push_back(0x10);
  out.push_back(static_cast<char>(name.size())); // <127 always here
  out.append(name);
  out.push_back(static_cast<char>(value.size()));
  out.append(value);
}

// HPACK literal with indexed name from the static table, never-indexed
// (RFC 7541 §6.2.3 with 4-bit prefixed name index).
void hpackIndexedName(std::string& out, int nameIndex, std::string_view value) {
  if (nameIndex < 15) {
    out.push_back(static_cast<char>(0x10 | nameIndex));
  } else {
    out.push_back(0x1F);
    out.push_back(static_cast<char>(nameIndex - 15)); // <128 for our uses
  }
  out.push_back(static_cast<char>(value.size()));
  out.append(value);
}

} // namespace

GrpcClient::~GrpcClient() {
  close();
}

void GrpcClient::close() {
  if (fd_ >= 0) {
    ::close(fd_);
    fd_ = -1;
  }
  nextStream_ = 1;
  hpackDecoder_ = hpack::Decoder(); // table state dies with the connection
}

bool GrpcClient::sendAll(std::string_view data) {
  while (!data.empty()) {
    ssize_t n = ::send(fd_, data.data(), data.size(), MSG_NOSIGNAL);
    if (n <= 0) {
      return false;
    }
    data.remove_prefix(static_cast<size_t>(n));
  }
  return true;
}

bool GrpcClient::recvExact(char* buf, size_t n,
                           std::chrono::steady_clock::time_point deadline,
                           const std::atomic<bool>* cancel) {
  // Poll-sliced, cancel-aware reads: a peer that sends a PARTIAL frame
  // and then stalls must not pin a cancelled shutdown until the call
  // deadline (which a clamped push window can stretch to minutes). On
  // failure errno says why: ECANCELED / ETIMEDOUT / the recv error
  // (0 from a clean peer close is mapped to ECONNRESET).
  size_t got = 0;
  while (got < n) {
    // Cancel check every iteration, not only in the poll path: a peer
    // that floods DATA keeps recv returning >0 forever, and the cancel
    // guarantee must not depend on the socket ever going empty.
    if (cancel && cancel->load()) {
      errno = ECANCELED;
      return false;
    }
    // recv first, poll only on EAGAIN: pending data (the common case on
    // a multi-MB XSpace drain) costs one syscall, not two; a stalled
    // peer lands in the cancel/deadline-sliced poll.
    ssize_t r = ::recv(fd_, buf + got, n - got, MSG_DONTWAIT);
    if (r > 0) {
      got += static_cast<size_t>(r);
      continue;
    }
    if (r == 0) {
      errno = ECONNRESET;
      return false;
    }
    if (errno != EAGAIN && errno != EWOULDBLOCK && errno != EINTR) {
      return false;
    }
    switch (pollWithCancel(fd_, POLLIN, deadline, cancel)) {
      case WaitResult::kReady:
        break;
      case WaitResult::kCancelled:
        errno = ECANCELED;
        return false;
      case WaitResult::kDeadline:
        errno = ETIMEDOUT;
        return false;
      case WaitResult::kError:
        return false;
    }
  }
  return true;
}

bool GrpcClient::sendFrame(uint8_t type, uint8_t flags, uint32_t stream,
                           std::string_view payload) {
  std::string hdr;
  hdr.push_back(static_cast<char>(payload.size() >> 16));
  hdr.push_back(static_cast<char>(payload.size() >> 8));
  hdr.push_back(static_cast<char>(payload.size()));
  hdr.push_back(static_cast<char>(type));
  hdr.push_back(static_cast<char>(flags));
  putU32(hdr, stream);
  return sendAll(hdr) && sendAll(payload);
}

bool GrpcClient::connect(std::string* error, int timeoutMs,
                         const std::atomic<bool>* cancel) {
  struct addrinfo hints{};
  hints.ai_family = AF_UNSPEC;
  hints.ai_socktype = SOCK_STREAM;
  struct addrinfo* res = nullptr;
  int rc = ::getaddrinfo(host_.c_str(), std::to_string(port_).c_str(), &hints,
                         &res);
  if (rc != 0 || !res) {
    *error = std::string("resolve failed: ") + gai_strerror(rc);
    return false;
  }
  // Non-blocking connect + 100ms poll slices: an unresponsive peer must
  // not pin a cancelled caller (daemon shutdown) for the full timeout.
  int fd = -1;
  int savedErrno = 0; // the FAILURE's errno: close()/freeaddrinfo() below
  for (auto* ai = res; ai; ai = ai->ai_next) { // may clobber errno itself
    fd = ::socket(ai->ai_family, ai->ai_socktype | SOCK_NONBLOCK,
                  ai->ai_protocol);
    if (fd < 0) {
      savedErrno = errno;
      continue;
    }
    int one = 1;
    ::setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    rc = ::connect(fd, ai->ai_addr, ai->ai_addrlen);
    if (rc < 0 && errno == EINPROGRESS) {
      auto deadline = std::chrono::steady_clock::now() +
          std::chrono::milliseconds(timeoutMs);
      switch (pollWithCancel(fd, POLLOUT, deadline, cancel)) {
        case WaitResult::kReady: {
          int soErr = 0;
          socklen_t soLen = sizeof(soErr);
          ::getsockopt(fd, SOL_SOCKET, SO_ERROR, &soErr, &soLen);
          rc = soErr == 0 ? 0 : -1;
          errno = soErr;
          break;
        }
        case WaitResult::kCancelled:
          rc = -1;
          errno = ECANCELED;
          break;
        case WaitResult::kDeadline:
          rc = -1;
          errno = ETIMEDOUT;
          break;
        case WaitResult::kError:
          rc = -1;
          break;
      }
    }
    if (rc == 0) {
      // Back to blocking mode; per-frame socket timeouts from here on.
      int fl = ::fcntl(fd, F_GETFL, 0);
      ::fcntl(fd, F_SETFL, fl & ~O_NONBLOCK);
      struct timeval tv{timeoutMs / 1000, (timeoutMs % 1000) * 1000};
      ::setsockopt(fd, SOL_SOCKET, SO_RCVTIMEO, &tv, sizeof(tv));
      ::setsockopt(fd, SOL_SOCKET, SO_SNDTIMEO, &tv, sizeof(tv));
      break;
    }
    savedErrno = errno;
    ::close(fd);
    fd = -1;
  }
  ::freeaddrinfo(res);
  if (fd < 0) {
    *error = "connect to " + host_ + ":" + std::to_string(port_) + " failed: " +
        std::strerror(savedErrno);
    return false;
  }
  fd_ = fd;
  nextStream_ = 1;

  // Preface + our SETTINGS (1MB initial stream window so sizeable metric
  // responses never stall on flow control; window and frame-size stay
  // modest ON PURPOSE — frequent WINDOW_UPDATE credit keeps the peer's
  // sends in steady small bursts that interleave with the streamed
  // disk write; advertising 1MB frames or a 4MB window slowed a push
  // capture when it was tried, not re-measured on the chip) + a
  // connection-window grant.
  std::string settings;
  settings.push_back(0x00);
  settings.push_back(0x04); // SETTINGS_INITIAL_WINDOW_SIZE
  putU32(settings, 1 << 20);
  settings.push_back(0x00);
  settings.push_back(0x02); // SETTINGS_ENABLE_PUSH = 0: a PUSH_PROMISE
  putU32(settings, 0); // would mutate HPACK state we'd have to track
  std::string grant;
  putU32(grant, (1 << 20) - 65535);
  if (!sendAll(kPreface) || !sendFrame(kFrameSettings, 0, 0, settings) ||
      !sendFrame(kFrameWindowUpdate, 0, 0, grant)) {
    *error = "HTTP/2 preface send failed";
    close();
    return false;
  }
  return true;
}

std::optional<std::string> GrpcClient::call(
    const std::string& path,
    std::string_view request,
    std::string* error,
    int timeoutMs,
    const std::atomic<bool>* cancel,
    GrpcCallStats* stats,
    const ResponseSink& onData) {
  std::string scratch;
  error = error ? error : &scratch;
  if (fd_ < 0 && !connect(error, timeoutMs, cancel)) {
    return std::nullopt;
  }
  // Per-call deadline: socket timeouts alone reset on every received
  // frame, so a server dribbling PINGs could hold the caller forever.
  // Reads are poll-sliced against this deadline in recvExact; only the
  // blocking sends still need a socket timeout, armed once per call.
  auto deadline =
      std::chrono::steady_clock::now() + std::chrono::milliseconds(timeoutMs);
  {
    struct timeval tv{timeoutMs / 1000,
                      static_cast<long>((timeoutMs % 1000) * 1000)};
    ::setsockopt(fd_, SOL_SOCKET, SO_SNDTIMEO, &tv, sizeof(tv));
  }
  uint32_t stream = nextStream_;
  nextStream_ += 2;

  // HEADERS: static-table indexed :method POST (3) and :scheme http (6);
  // the rest as never-indexed literals (no dynamic table, no Huffman).
  std::string hpack;
  hpack.push_back(static_cast<char>(0x83)); // :method: POST
  hpack.push_back(static_cast<char>(0x86)); // :scheme: http
  hpackIndexedName(hpack, 4, path); // :path
  hpackIndexedName(hpack, 1, host_); // :authority
  hpackIndexedName(hpack, 31, "application/grpc"); // content-type
  hpackLiteral(hpack, "te", "trailers");

  // gRPC message framing: 1-byte compressed flag + u32be length.
  std::string body;
  body.push_back(0x00);
  putU32(body, static_cast<uint32_t>(request.size()));
  body.append(request);

  if (!sendFrame(kFrameHeaders, kFlagEndHeaders, stream, hpack) ||
      !sendFrame(kFrameData, kFlagEndStream, stream, body)) {
    *error = "request send failed";
    close();
    return std::nullopt;
  }
  auto requestSent = std::chrono::steady_clock::now();
  auto sinceRequestMs = [&requestSent]() {
    return std::chrono::duration_cast<std::chrono::milliseconds>(
               std::chrono::steady_clock::now() - requestSent)
        .count();
  };

  // Read frames until our stream ends. DATA accumulates — or, with an
  // onData sink, is de-framed incrementally and forwarded as it arrives
  // (the gRPC 5-byte message prefix parsed across frame boundaries);
  // HEADERS and trailers are HPACK-decoded (grpc-status must never be
  // dropped); everything else is protocol upkeep (SETTINGS/PING ACKs)
  // or skipped.
  std::string data;
  uint64_t dataBytes = 0;
  size_t msgPrefixGot = 0; // bytes of the 5-byte message prefix seen
  uint8_t msgPrefix[5] = {0, 0, 0, 0, 0};
  uint64_t msgRemaining = 0; // message payload bytes still expected
  uint64_t consumedSinceGrant = 0;
  bool streamEnded = false;
  std::string grpcStatus, grpcMessage, httpStatus;
  // CONTINUATION accumulation: every header block on the connection must
  // be decoded (HPACK table state is connection-wide), not only ours.
  std::string headerBlock;
  uint32_t headerStream = 0;
  bool accumulatingHeaders = false;
  bool headersEndStream = false;
  auto processHeaderBlock = [&]() -> bool {
    std::vector<hpack::Header> headers;
    if (!hpackDecoder_.decode(headerBlock, &headers)) {
      return false; // table now unsynchronized: connection must die
    }
    if (headerStream == stream) {
      for (const auto& h : headers) {
        if (h.name == "grpc-status") {
          grpcStatus = h.value;
        } else if (h.name == "grpc-message") {
          grpcMessage = h.value;
        } else if (h.name == ":status") {
          httpStatus = h.value;
        }
      }
      if (headersEndStream) {
        streamEnded = true;
      }
    }
    return true;
  };
  while (!streamEnded) {
    if (std::chrono::steady_clock::now() >= deadline) {
      *error = "call deadline exceeded";
      close();
      return std::nullopt;
    }
    // recvExact is cancel-aware down to 100ms poll slices, mid-frame
    // included: a raised token aborts a multi-second server-side window
    // (Profile holds the stream open for its whole duration) — and a
    // peer that stalls after a partial frame — without waiting out the
    // call deadline.
    char hdr[9];
    if (!recvExact(hdr, 9, deadline, cancel)) {
      *error = errno == ECANCELED ? "call cancelled"
          : errno == ETIMEDOUT   ? "call deadline exceeded"
                                 : "connection closed mid-response";
      close();
      return std::nullopt;
    }
    uint32_t len = (static_cast<uint8_t>(hdr[0]) << 16) |
        (static_cast<uint8_t>(hdr[1]) << 8) | static_cast<uint8_t>(hdr[2]);
    uint8_t type = static_cast<uint8_t>(hdr[3]);
    uint8_t flags = static_cast<uint8_t>(hdr[4]);
    uint32_t sid = ((static_cast<uint8_t>(hdr[5]) & 0x7F) << 24) |
        (static_cast<uint8_t>(hdr[6]) << 16) |
        (static_cast<uint8_t>(hdr[7]) << 8) | static_cast<uint8_t>(hdr[8]);
    if (len > (1 << 24)) {
      *error = "oversized frame";
      close();
      return std::nullopt;
    }
    std::string payload(len, '\0');
    if (len && !recvExact(payload.data(), len, deadline, cancel)) {
      *error = errno == ECANCELED ? "call cancelled"
          : errno == ETIMEDOUT   ? "call deadline exceeded"
                                 : "connection closed mid-frame";
      close();
      return std::nullopt;
    }
    switch (type) {
      case kFrameData:
        consumedSinceGrant += len;
        if (sid == stream) {
          if (stats && stats->firstDataMs < 0 && len > 0) {
            stats->firstDataMs = sinceRequestMs();
          }
          dataBytes += len;
          if (onData) {
            // Incremental de-framing: finish the 5-byte message prefix
            // (possibly split across frames), then forward message
            // payload to the sink slice by slice. Bytes past the
            // message end are swallowed, as the buffered path's
            // substr() always did.
            std::string_view rest(payload);
            while (!rest.empty()) {
              if (msgPrefixGot < sizeof(msgPrefix)) {
                size_t take = std::min(
                    sizeof(msgPrefix) - msgPrefixGot, rest.size());
                std::memcpy(msgPrefix + msgPrefixGot, rest.data(), take);
                msgPrefixGot += take;
                rest.remove_prefix(take);
                if (msgPrefixGot == sizeof(msgPrefix)) {
                  if (msgPrefix[0] != 0x00) {
                    *error = "compressed response not supported";
                    close();
                    return std::nullopt;
                  }
                  msgRemaining = (static_cast<uint64_t>(msgPrefix[1]) << 24) |
                      (static_cast<uint64_t>(msgPrefix[2]) << 16) |
                      (static_cast<uint64_t>(msgPrefix[3]) << 8) |
                      static_cast<uint64_t>(msgPrefix[4]);
                }
                continue;
              }
              size_t take = static_cast<size_t>(
                  std::min<uint64_t>(msgRemaining, rest.size()));
              if (take == 0) {
                break; // trailing bytes beyond the message: ignore
              }
              if (!onData(rest.substr(0, take))) {
                *error = "response sink failed";
                close();
                return std::nullopt;
              }
              msgRemaining -= take;
              rest.remove_prefix(take);
            }
          } else {
            data += payload;
          }
          if (flags & kFlagEndStream) {
            streamEnded = true;
          }
        }
        // Replenish flow-control windows mid-response: a reply larger
        // than the initial stream window (e.g. a multi-MB profiler
        // XSpace) would otherwise stall until the deadline.
        if (consumedSinceGrant >= (512u << 10) && !streamEnded) {
          std::string grant;
          putU32(grant, static_cast<uint32_t>(consumedSinceGrant));
          sendFrame(kFrameWindowUpdate, 0, 0, grant);
          sendFrame(kFrameWindowUpdate, 0, stream, grant);
          consumedSinceGrant = 0;
        }
        break;
      case kFrameHeaders: {
        if (accumulatingHeaders) {
          // A new HEADERS before the previous block's CONTINUATIONs
          // finished would clobber an undecoded fragment — an HPACK
          // desync we must not survive silently.
          *error = "HEADERS while a header block is still open";
          close();
          return std::nullopt;
        }
        std::string_view block(payload);
        uint8_t pad = 0;
        if (flags & kFlagPadded) {
          if (block.empty()) {
            *error = "malformed HEADERS (empty padded frame)";
            close();
            return std::nullopt;
          }
          pad = static_cast<uint8_t>(block[0]);
          block.remove_prefix(1);
        }
        if (flags & kFlagPriority) {
          if (block.size() < 5) {
            *error = "malformed HEADERS (short priority section)";
            close();
            return std::nullopt;
          }
          block.remove_prefix(5);
        }
        if (pad > block.size()) {
          *error = "malformed HEADERS (padding exceeds frame)";
          close();
          return std::nullopt;
        }
        block.remove_suffix(pad);
        headerBlock.assign(block);
        headerStream = sid;
        headersEndStream = flags & kFlagEndStream;
        if (flags & kFlagEndHeaders) {
          if (!processHeaderBlock()) {
            *error = "malformed response headers (HPACK)";
            close();
            return std::nullopt;
          }
        } else {
          accumulatingHeaders = true;
        }
        break;
      }
      case kFramePushPromise:
        // Push is disabled in our SETTINGS; a server sending one anyway
        // is a protocol error — and its header block would silently
        // desynchronize the HPACK table if skipped.
        *error = "unexpected PUSH_PROMISE frame";
        close();
        return std::nullopt;
      case kFrameContinuation:
        if (!accumulatingHeaders || sid != headerStream) {
          *error = "unexpected CONTINUATION frame";
          close();
          return std::nullopt;
        }
        headerBlock += payload;
        if (flags & kFlagEndHeaders) {
          accumulatingHeaders = false;
          if (!processHeaderBlock()) {
            *error = "malformed response headers (HPACK)";
            close();
            return std::nullopt;
          }
        }
        break;
      case kFrameSettings:
        if (!(flags & kFlagAck)) {
          sendFrame(kFrameSettings, kFlagAck, 0, "");
        }
        break;
      case kFramePing:
        if (!(flags & kFlagAck)) {
          sendFrame(kFramePing, kFlagAck, 0, payload);
        }
        break;
      case kFrameRstStream:
        if (sid == stream) {
          *error = "stream reset by server";
          return std::nullopt; // connection itself stays usable
        }
        break;
      case kFrameGoaway:
        *error = "server sent GOAWAY";
        close();
        return std::nullopt;
      case kFrameWindowUpdate:
      default:
        break; // ignore
    }
  }

  if (stats) {
    stats->streamMs = sinceRequestMs();
    stats->respBytes = static_cast<int64_t>(dataBytes);
  }

  // Replenish the connection-level window for DATA not yet granted back
  // mid-stream — without this, a reused connection deterministically
  // stalls once cumulative responses exhaust the one-time grant.
  if (consumedSinceGrant > 0) {
    std::string grant;
    putU32(grant, static_cast<uint32_t>(consumedSinceGrant));
    sendFrame(kFrameWindowUpdate, 0, 0, grant);
  }

  // Status gate before any message parsing: a non-OK grpc-status fails
  // the call with the server's own code + message even when DATA frames
  // arrived first (partial results from a failed call are not results),
  // and trailers-only errors surface the real status.
  if (!httpStatus.empty() && httpStatus != "200") {
    *error = "HTTP status " + httpStatus + " from server";
    return std::nullopt;
  }
  if (!grpcStatus.empty() && grpcStatus != "0") {
    errno = 0;
    long code = std::strtol(grpcStatus.c_str(), nullptr, 10);
    *error = std::string(grpcStatusName(errno ? -1 : code)) +
        " (grpc-status " + grpcStatus + ")";
    if (!grpcMessage.empty()) {
      *error += ": " + percentDecode(grpcMessage);
    }
    return std::nullopt;
  }

  // De-frame the gRPC message. The streaming path already did it
  // incrementally: just validate completeness — the sink's bytes are
  // only now (OK status, full message) known good.
  if (onData) {
    if (msgPrefixGot < sizeof(msgPrefix)) {
      *error = "no response message in OK-status stream";
      return std::nullopt;
    }
    if (msgRemaining != 0) {
      *error = "truncated response message";
      return std::nullopt;
    }
    return std::string();
  }
  if (data.size() < 5) {
    *error = "no response message in OK-status stream";
    return std::nullopt;
  }
  if (data[0] != 0x00) {
    *error = "compressed response not supported";
    return std::nullopt;
  }
  uint32_t mlen = (static_cast<uint8_t>(data[1]) << 24) |
      (static_cast<uint8_t>(data[2]) << 16) |
      (static_cast<uint8_t>(data[3]) << 8) | static_cast<uint8_t>(data[4]);
  if (data.size() - 5 < mlen) {
    *error = "truncated response message";
    return std::nullopt;
  }
  return data.substr(5, mlen);
}

} // namespace dynotpu
