// POSIX TCP helpers with deadlines for the control-plane wire protocol.
// Equivalent role to the reference's src/net.rs (channel connect with
// keepalive + backoff retry) but for raw sockets.
#pragma once

#include <chrono>
#include <cstdint>
#include <optional>
#include <string>

namespace tft {

using Clock = std::chrono::steady_clock;
using TimePoint = Clock::time_point;
using Millis = std::chrono::milliseconds;

inline TimePoint deadline_from_ms(int64_t ms) { return Clock::now() + Millis(ms); }
int64_t ms_until(TimePoint deadline);

// RAII socket fd.
class Socket {
 public:
  Socket() = default;
  explicit Socket(int fd) : fd_(fd) {}
  Socket(const Socket&) = delete;
  Socket& operator=(const Socket&) = delete;
  Socket(Socket&& o) noexcept : fd_(o.fd_) { o.fd_ = -1; }
  Socket& operator=(Socket&& o) noexcept;
  ~Socket();

  int fd() const { return fd_; }
  bool valid() const { return fd_ >= 0; }
  void close();
  // Wake any thread blocked in recv/send on this socket WITHOUT freeing the
  // fd: safe to call from another thread (close() would race the user and
  // the freed fd number could be reallocated mid-syscall).
  void shutdown_rdwr();

  // All throw std::runtime_error on failure; timeout errors contain "timed out".
  void send_all(const void* data, size_t len, TimePoint deadline);
  void recv_all(void* data, size_t len, TimePoint deadline);
  // Peek up to len bytes without consuming (used for HTTP-vs-frame sniffing).
  size_t peek(void* data, size_t len, TimePoint deadline);

 private:
  int fd_ = -1;
};

// Listener bound to host:port (port 0 -> ephemeral). Accept with timeout.
class Listener {
 public:
  // bind format: "host:port". Throws on failure.
  explicit Listener(const std::string& bind);
  ~Listener();
  Listener(const Listener&) = delete;

  // Local port actually bound.
  int port() const { return port_; }
  // Blocks up to timeout; returns nullopt on timeout, throws on error.
  // Wakes and returns nullopt promptly after shutdown().
  std::optional<Socket> accept(Millis timeout);
  void shutdown();

 private:
  int fd_ = -1;
  int port_ = 0;
};

// Connect with deadline; retries with backoff until deadline (reference
// behavior: src/net.rs:16-42 connect retry loop).
Socket connect_with_retry(const std::string& host, int port, TimePoint deadline);

// Parse "host:port" (supports "[v6]:port").
std::pair<std::string, int> split_host_port(const std::string& addr);

std::string local_hostname();

// A whole buffer over a socket the caller owns (a Python socket's fd, in
// either blocking mode: the host ring's frames, process_group.py), in one
// call outside the interpreter. idle_ms: how long the peer may make no
// progress (negative: for ever). more: further bytes of the same message
// follow (the frame's header). Returns 0 when done, kFdTimedOut,
// kFdClosed (the peer closed, or shut down under us), or -errno.
constexpr int kFdTimedOut = 1, kFdClosed = 2;
int fd_send_all(int fd, const void* data, size_t len, int64_t idle_ms, bool more);
int fd_recv_all(int fd, void* data, size_t len, int64_t idle_ms);

}  // namespace tft
