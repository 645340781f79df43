#include "net.h"

#include <arpa/inet.h>
#include <errno.h>
#include <fcntl.h>
#include <netdb.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <string.h>
#include <sys/socket.h>
#include <unistd.h>

#include <algorithm>
#include <stdexcept>
#include <thread>

namespace tft {

int64_t ms_until(TimePoint deadline) {
  auto d = std::chrono::duration_cast<Millis>(deadline - Clock::now()).count();
  return d;
}

namespace {

[[noreturn]] void throw_errno(const std::string& what) {
  throw std::runtime_error(what + ": " + strerror(errno));
}

[[noreturn]] void throw_timeout(const std::string& what) {
  throw std::runtime_error(what + ": timed out");
}

void set_nonblocking(int fd, bool nb) {
  int flags = fcntl(fd, F_GETFL, 0);
  if (flags < 0) throw_errno("fcntl");
  if (nb) flags |= O_NONBLOCK; else flags &= ~O_NONBLOCK;
  if (fcntl(fd, F_SETFL, flags) < 0) throw_errno("fcntl");
}

// Wait for readability/writability up to deadline. events: POLLIN/POLLOUT.
bool wait_fd(int fd, short events, TimePoint deadline) {
  while (true) {
    int64_t ms = ms_until(deadline);
    if (ms <= 0) return false;
    struct pollfd pfd{fd, events, 0};
    int rc = poll(&pfd, 1, static_cast<int>(std::min<int64_t>(ms, 1000)));
    if (rc < 0) {
      if (errno == EINTR) continue;
      throw_errno("poll");
    }
    if (rc > 0) return true;
  }
}

}  // namespace

Socket& Socket::operator=(Socket&& o) noexcept {
  if (this != &o) {
    close();
    fd_ = o.fd_;
    o.fd_ = -1;
  }
  return *this;
}

Socket::~Socket() { close(); }

void Socket::shutdown_rdwr() {
  if (fd_ >= 0) ::shutdown(fd_, SHUT_RDWR);
}

void Socket::close() {
  if (fd_ >= 0) {
    ::close(fd_);
    fd_ = -1;
  }
}

void Socket::send_all(const void* data, size_t len, TimePoint deadline) {
  const char* p = static_cast<const char*>(data);
  size_t sent = 0;
  while (sent < len) {
    ssize_t n = ::send(fd_, p + sent, len - sent, MSG_NOSIGNAL | MSG_DONTWAIT);
    if (n > 0) {
      sent += static_cast<size_t>(n);
      continue;
    }
    if (n < 0 && errno != EAGAIN && errno != EWOULDBLOCK && errno != EINTR)
      throw_errno("send");
    if (!wait_fd(fd_, POLLOUT, deadline)) throw_timeout("send");
  }
}

void Socket::recv_all(void* data, size_t len, TimePoint deadline) {
  char* p = static_cast<char*>(data);
  size_t got = 0;
  while (got < len) {
    ssize_t n = ::recv(fd_, p + got, len - got, MSG_DONTWAIT);
    if (n > 0) {
      got += static_cast<size_t>(n);
      continue;
    }
    if (n == 0) throw std::runtime_error("recv: connection closed");
    if (errno != EAGAIN && errno != EWOULDBLOCK && errno != EINTR)
      throw_errno("recv");
    if (!wait_fd(fd_, POLLIN, deadline)) throw_timeout("recv");
  }
}

size_t Socket::peek(void* data, size_t len, TimePoint deadline) {
  while (true) {
    ssize_t n = ::recv(fd_, data, len, MSG_DONTWAIT | MSG_PEEK);
    if (n > 0) return static_cast<size_t>(n);
    if (n == 0) throw std::runtime_error("peek: connection closed");
    if (errno != EAGAIN && errno != EWOULDBLOCK && errno != EINTR)
      throw_errno("peek");
    if (!wait_fd(fd_, POLLIN, deadline)) throw_timeout("peek");
  }
}

Listener::Listener(const std::string& bind) {
  auto [host, port] = split_host_port(bind);
  fd_ = ::socket(AF_INET, SOCK_STREAM, 0);
  if (fd_ < 0) throw_errno("socket");
  int one = 1;
  setsockopt(fd_, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
  struct sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_port = htons(static_cast<uint16_t>(port));
  if (host.empty() || host == "0.0.0.0" || host == "::" || host == "[::]") {
    addr.sin_addr.s_addr = INADDR_ANY;
  } else if (inet_pton(AF_INET, host.c_str(), &addr.sin_addr) != 1) {
    // resolve hostname
    struct addrinfo hints{};
    hints.ai_family = AF_INET;
    hints.ai_socktype = SOCK_STREAM;
    struct addrinfo* res = nullptr;
    if (getaddrinfo(host.c_str(), nullptr, &hints, &res) != 0 || !res) {
      ::close(fd_);
      throw std::runtime_error("cannot resolve bind host: " + host);
    }
    addr.sin_addr = reinterpret_cast<sockaddr_in*>(res->ai_addr)->sin_addr;
    freeaddrinfo(res);
  }
  if (::bind(fd_, reinterpret_cast<sockaddr*>(&addr), sizeof(addr)) < 0) {
    int e = errno;
    ::close(fd_);
    errno = e;
    throw_errno("bind " + bind);
  }
  if (::listen(fd_, 128) < 0) {
    int e = errno;
    ::close(fd_);
    errno = e;
    throw_errno("listen");
  }
  socklen_t alen = sizeof(addr);
  getsockname(fd_, reinterpret_cast<sockaddr*>(&addr), &alen);
  port_ = ntohs(addr.sin_port);
  set_nonblocking(fd_, true);
}

Listener::~Listener() { shutdown(); }

void Listener::shutdown() {
  if (fd_ >= 0) {
    ::shutdown(fd_, SHUT_RDWR);
    ::close(fd_);
    fd_ = -1;
  }
}

std::optional<Socket> Listener::accept(Millis timeout) {
  TimePoint deadline = Clock::now() + timeout;
  while (true) {
    if (fd_ < 0) return std::nullopt;
    int cfd = ::accept(fd_, nullptr, nullptr);
    if (cfd >= 0) {
      set_nonblocking(cfd, true);
      int one = 1;
      setsockopt(cfd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
      return Socket(cfd);
    }
    if (errno != EAGAIN && errno != EWOULDBLOCK && errno != EINTR) {
      if (errno == EBADF || errno == EINVAL) return std::nullopt;  // shut down
      throw_errno("accept");
    }
    int64_t ms = ms_until(deadline);
    if (ms <= 0) return std::nullopt;
    struct pollfd pfd{fd_, POLLIN, 0};
    poll(&pfd, 1, static_cast<int>(std::min<int64_t>(ms, 200)));
  }
}

Socket connect_with_retry(const std::string& host, int port, TimePoint deadline) {
  Millis backoff(10);
  std::string last_err = "unknown";
  while (true) {
    try {
      struct addrinfo hints{};
      hints.ai_family = AF_INET;
      hints.ai_socktype = SOCK_STREAM;
      struct addrinfo* res = nullptr;
      std::string h = host.empty() ? "127.0.0.1" : host;
      if (h == "0.0.0.0") h = "127.0.0.1";
      if (getaddrinfo(h.c_str(), std::to_string(port).c_str(), &hints, &res) != 0 ||
          !res)
        throw std::runtime_error("cannot resolve " + h);
      int fd = ::socket(res->ai_family, SOCK_STREAM, 0);
      if (fd < 0) {
        freeaddrinfo(res);
        throw_errno("socket");
      }
      set_nonblocking(fd, true);
      int rc = ::connect(fd, res->ai_addr, res->ai_addrlen);
      freeaddrinfo(res);
      if (rc < 0 && errno != EINPROGRESS) {
        ::close(fd);
        throw_errno("connect");
      }
      if (rc < 0) {
        if (!wait_fd(fd, POLLOUT, deadline)) {
          ::close(fd);
          throw_timeout("connect");
        }
        int err = 0;
        socklen_t len = sizeof(err);
        getsockopt(fd, SOL_SOCKET, SO_ERROR, &err, &len);
        if (err != 0) {
          ::close(fd);
          errno = err;
          throw_errno("connect");
        }
      }
      int one = 1;
      setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
      setsockopt(fd, SOL_SOCKET, SO_KEEPALIVE, &one, sizeof(one));
      return Socket(fd);
    } catch (const std::exception& e) {
      last_err = e.what();
      if (std::string(e.what()).find("timed out") != std::string::npos ||
          ms_until(deadline) <= 0) {
        throw std::runtime_error("connect to " + host + ":" +
                                 std::to_string(port) +
                                 " failed (timed out): " + last_err);
      }
      std::this_thread::sleep_for(
          std::min<Millis>(backoff, Millis(std::max<int64_t>(ms_until(deadline), 1))));
      backoff = std::min<Millis>(backoff * 2, Millis(1000));
    }
  }
}

std::pair<std::string, int> split_host_port(const std::string& addr) {
  std::string a = addr;
  // strip scheme
  auto scheme = a.find("://");
  if (scheme != std::string::npos) a = a.substr(scheme + 3);
  // strip path
  auto slash = a.find('/');
  if (slash != std::string::npos) a = a.substr(0, slash);
  if (!a.empty() && a[0] == '[') {
    auto close = a.find(']');
    if (close == std::string::npos) throw std::runtime_error("bad address: " + addr);
    std::string host = a.substr(1, close - 1);
    int port = 0;
    if (close + 1 < a.size() && a[close + 1] == ':')
      port = std::stoi(a.substr(close + 2));
    return {host, port};
  }
  auto colon = a.rfind(':');
  if (colon == std::string::npos) return {a, 0};
  return {a.substr(0, colon), std::stoi(a.substr(colon + 1))};
}

namespace {

// 0 ready, kFdTimedOut, or -errno.
int wait_fd_idle(int fd, short events, int64_t idle_ms) {
  while (true) {
    struct pollfd pfd{fd, events, 0};
    int rc = poll(&pfd, 1, idle_ms < 0 ? -1 : static_cast<int>(
        std::min<int64_t>(idle_ms, 1 << 30)));
    if (rc > 0) return 0;
    if (rc == 0) return kFdTimedOut;
    if (errno != EINTR) return -errno;
  }
}

}  // namespace

int fd_send_all(int fd, const void* data, size_t len, int64_t idle_ms,
                bool more) {
  const char* p = static_cast<const char*>(data);
  const int flags = MSG_NOSIGNAL | MSG_DONTWAIT | (more ? MSG_MORE : 0);
  size_t sent = 0;
  while (sent < len) {
    ssize_t n = ::send(fd, p + sent, len - sent, flags);
    if (n > 0) {
      sent += static_cast<size_t>(n);
      continue;
    }
    if (n < 0 && errno == EPIPE) return kFdClosed;
    if (n < 0 && errno != EAGAIN && errno != EWOULDBLOCK && errno != EINTR)
      return -errno;
    if (int rc = wait_fd_idle(fd, POLLOUT, idle_ms)) return rc;
  }
  return 0;
}

int fd_recv_all(int fd, void* data, size_t len, int64_t idle_ms) {
  char* p = static_cast<char*>(data);
  size_t got = 0;
  while (got < len) {
    ssize_t n = ::recv(fd, p + got, len - got, MSG_DONTWAIT);
    if (n > 0) {
      got += static_cast<size_t>(n);
      continue;
    }
    if (n == 0) return kFdClosed;
    if (errno != EAGAIN && errno != EWOULDBLOCK && errno != EINTR)
      return -errno;
    if (int rc = wait_fd_idle(fd, POLLIN, idle_ms)) return rc;
  }
  return 0;
}

std::string local_hostname() {
  char buf[256];
  if (gethostname(buf, sizeof(buf)) != 0) return "localhost";
  buf[sizeof(buf) - 1] = '\0';
  return std::string(buf);
}

}  // namespace tft
