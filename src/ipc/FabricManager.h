// dynolog_tpu: message-level layer over EndPoint.
// Behavioral parity: reference dynolog/src/ipcfabric/FabricManager.h —
// Message = 40-byte metadata (u64 payload size + char[32] ASCII type tag) +
// payload in a single datagram (:30-43), sync_send with exponential-backoff
// retries (:111-138), peek-metadata-then-read-body two-phase receive
// (:140-194), thread-safe received-message deque. Wire identical to the
// reference so libkineto's IpcFabricConfigClient interoperates. The Python
// client shim (dynolog_tpu/client/ipc.py) implements the same framing with
// struct.pack("<Q32s").
#pragma once

#include <deque>
#include <memory>
#include <mutex>
#include <thread>

#include "src/ipc/Endpoint.h"

namespace dynotpu {
namespace ipc {

constexpr int kTypeSize = 32;

struct Metadata {
  uint64_t size = 0;
  char type[kTypeSize] = "";
};
static_assert(sizeof(Metadata) == 40, "wire format requires 40-byte metadata");

struct Message {
  Metadata metadata;
  std::unique_ptr<unsigned char[]> buf;
  std::string src; // sender endpoint name (filled on receive)

  static std::unique_ptr<Message> create(
      const void* data,
      size_t size,
      const std::string& type) {
    auto msg = std::make_unique<Message>();
    DYN_CHECK(type.size() < kTypeSize, "message type tag too long");
    std::memcpy(msg->metadata.type, type.c_str(), type.size() + 1);
    msg->metadata.size = size;
    msg->buf = std::make_unique<unsigned char[]>(size);
    if (size > 0) {
      std::memcpy(msg->buf.get(), data, size);
    }
    return msg;
  }

  static std::unique_ptr<Message> createFromString(
      const std::string& payload,
      const std::string& type) {
    return create(payload.data(), payload.size(), type);
  }

  template <class T>
  static std::unique_ptr<Message> createFromPod(
      const T& pod,
      const std::string& type) {
    static_assert(std::is_trivially_copyable<T>::value, "POD required");
    return create(&pod, sizeof(pod), type);
  }

  std::string payloadString() const {
    return std::string(reinterpret_cast<const char*>(buf.get()), metadata.size);
  }
};

class FabricManager {
 public:
  FabricManager(const FabricManager&) = delete;
  FabricManager& operator=(const FabricManager&) = delete;

  // nullptr when the endpoint cannot be bound (e.g. name already taken) —
  // callers degrade gracefully, as with the reference factory.
  static std::unique_ptr<FabricManager> factory(
      const std::string& endpointName = "") {
    try {
      return std::unique_ptr<FabricManager>(new FabricManager(endpointName));
    } catch (const std::exception& e) {
      DLOG_ERROR << "FabricManager init failed: " << e.what();
      return nullptr;
    }
  }

  // Blocking send with exponential backoff; false once retries exhaust.
  bool sync_send(
      const Message& msg,
      const std::string& destName,
      int numRetries = 10,
      int sleepTimeUs = 10000) {
    if (destName.empty()) {
      DLOG_ERROR << "sync_send: empty destination";
      return false;
    }
    std::vector<Payload> iov{
        {const_cast<Metadata*>(&msg.metadata), sizeof(Metadata)},
        {msg.buf.get(), msg.metadata.size},
    };
    for (int attempt = 0; attempt < numRetries; ++attempt) {
      if (endpoint_.trySend(destName, iov)) {
        return true;
      }
      std::this_thread::sleep_for(std::chrono::microseconds(sleepTimeUs));
      sleepTimeUs *= 2;
    }
    DLOG_ERROR << "sync_send to " << destName << " failed after retries";
    return false;
  }

  // Largest payload accepted from a peer. The socket is reachable by any
  // local process, so the peeked size field is untrusted input.
  static constexpr uint64_t kMaxPayload = 1 << 20;

  // Polls once: peeks the metadata, then reads metadata+payload in one
  // datagram. Returns true when a message was enqueued.
  // hot-path: runs every pass of the monitor thread; must never block.
  bool recv() {
    Metadata metadata;
    std::vector<Payload> peekIov{{&metadata, sizeof(Metadata)}};
    ssize_t peeked = endpoint_.tryRecv(peekIov, nullptr, /*peek=*/true);
    if (peeked < 0) {
      return false;
    }
    if (static_cast<size_t>(peeked) < sizeof(Metadata) ||
        metadata.size > kMaxPayload) {
      // Malformed or hostile header: consume and drop the datagram.
      DLOG_WARNING << "ipc: dropping malformed datagram (" << peeked
                   << " bytes, claimed payload " << metadata.size << ")";
      endpoint_.tryRecv(peekIov, nullptr, /*peek=*/false);
      return false;
    }
    auto msg = std::make_unique<Message>();
    msg->metadata = metadata;
    msg->buf = std::make_unique<unsigned char[]>(metadata.size);
    std::vector<Payload> iov{
        {&msg->metadata, sizeof(Metadata)},
        {msg->buf.get(), metadata.size},
    };
    std::string src;
    ssize_t got = endpoint_.tryRecv(iov, &src, /*peek=*/false);
    if (got < 0) {
      return false; // raced with another reader
    }
    if (static_cast<uint64_t>(got) != sizeof(Metadata) + msg->metadata.size) {
      // Peer lied about the payload length; don't hand uninitialized bytes
      // to message handlers.
      DLOG_WARNING << "ipc: dropping truncated datagram from '" << src
                   << "' (" << got << " bytes, claimed "
                   << sizeof(Metadata) + msg->metadata.size << ")";
      return false;
    }
    msg->src = src;
    std::lock_guard<std::mutex> lock(mutex_);
    queue_.push_back(std::move(msg));
    return true;
  }

  // Blocking recv with bounded retries.
  bool poll_recv(int maxRetries, int sleepTimeUs = 10000) {
    for (int i = 0; i < maxRetries; ++i) {
      if (recv()) {
        return true;
      }
      std::this_thread::sleep_for(std::chrono::microseconds(sleepTimeUs));
    }
    return false;
  }

  // The bound socket, for a caller that waits in poll(2) for the next
  // datagram instead of retrying recv() on a timer.
  int fd() const {
    return endpoint_.fd();
  }

  std::unique_ptr<Message> retrieve_msg() {
    std::lock_guard<std::mutex> lock(mutex_);
    if (queue_.empty()) {
      return nullptr;
    }
    auto msg = std::move(queue_.front());
    queue_.pop_front();
    return msg;
  }

 private:
  explicit FabricManager(const std::string& endpointName)
      : endpoint_(endpointName) {}

  // Bound once at construction; sendto/recvfrom on a bound datagram
  // socket are kernel-atomic and safe from concurrent threads.
  EndPoint endpoint_; // unguarded(thread-safe kernel socket ops)
  std::mutex mutex_;
  std::deque<std::unique_ptr<Message>> queue_; // guarded_by(mutex_)
};

} // namespace ipc
} // namespace dynotpu
