// interpose — LD_PRELOAD syscall-interposition shim (pipelined +
// speculative output commit).
//
// Native-equivalent of the reference's spec_hooks.cpp: hooks
// __libc_start_main (init before the app's main, :48-100), accept/accept4
// (:102-141), read (:161-178) and close (:143-159), filtering sockets via
// fstat S_IFSOCK (:113-116). Where the reference calls straight into the
// in-process proxy (proxy_on_accept/read/close, rsm-interface.h:12-15),
// this shim forwards each event over a Unix domain socket to the replica
// driver daemon.
//
// TWO commit-wait disciplines:
//
// * SYNC (RP_SPEC=0): the calling thread blocks inside read() until the
//   driver acks — the reference's spin-until-committed-and-applied
//   semantics (proxy.c:160), pipelined across threads (each app thread
//   waits only for ITS OWN event).
//
// * SPECULATIVE (default): read() forwards the inbound bytes to the
//   driver and returns IMMEDIATELY — the app executes on not-yet-
//   committed input — while the shim additionally hooks the app's
//   OUTPUT syscalls (write/send/writev/sendmsg) on tracked client fds
//   and holds every reply until the commit frontier covers all input
//   events forwarded before that reply was produced (output commit).
//   Externally the guarantee is unchanged — a client that HAS a reply
//   knows its request committed — but the app's event loop never
//   stalls, so a single-threaded server (redis) keeps a deep pipeline
//   of events in flight instead of one-read-per-commit-RTT. This is
//   the TPU-native redesign of the reference's µs-scale blocking hot
//   path: with a host-loop commit latency in the milliseconds, blocking
//   the app thread caps throughput at one read-buffer per RTT;
//   speculation + output commit decouples app execution rate from
//   commit latency entirely. Mis-speculation (a deposed leader whose
//   app consumed input that never committed) is surfaced to the driver,
//   which quarantines the app until it is restarted and rebuilt from
//   the committed store (ClusterDriver.reset_app).
//
// Env:
//   RP_PROXY_SOCK  — path of the driver's Unix socket. Unset => all hooks
//                    pass through untouched (the app runs unreplicated).
//   RP_SPEC        — "0" selects the SYNC discipline (default "1").
//
// Wire format (little-endian), unchanged from the sync-only revision:
//   request : [u8 op][u32 seq][i32 fd][u32 len][len bytes]
//                                  op: 1=HELLO 2=CONNECT 3=SEND 4=CLOSE
//   response: [u32 seq][i32 status]   >=0 ok / pass; <0 drop connection
//   HELLO carries one payload byte: bit0 = speculative mode.
//   A CONNECT's status decides the connection ONCE: <0 sever, 0 track, 1
//   pass and forget (the driver's own replay connection: no read of it is
//   forwarded, no reply on it held, no close of it reported). A driver
//   that only ever answers 0 tracks such a connection as it did; a shim
//   from before the 1 reads it as "ok" and tracks it: both still work.
//
// Build: make -C native  ->  interpose.so

#include <cstdint>
#include <cstdio>
#include <cstring>

#include <deque>
#include <string>

#include <dlfcn.h>
#include <errno.h>
#include <fcntl.h>
#include <netinet/in.h>
#include <poll.h>
#include <pthread.h>
#include <stdlib.h>
#include <sys/socket.h>
#include <sys/stat.h>
#include <sys/uio.h>
#include <sys/un.h>
#include <unistd.h>

namespace {

enum Op : uint8_t { OP_HELLO = 1, OP_CONNECT = 2, OP_SEND = 3, OP_CLOSE = 4 };

using accept_fn = int (*)(int, struct sockaddr*, socklen_t*);
using accept4_fn = int (*)(int, struct sockaddr*, socklen_t*, int);
using read_fn = ssize_t (*)(int, void*, size_t);
using write_fn = ssize_t (*)(int, const void*, size_t);
using send_fn = ssize_t (*)(int, const void*, size_t, int);
using writev_fn = ssize_t (*)(int, const struct iovec*, int);
using sendmsg_fn = ssize_t (*)(int, const struct msghdr*, int);
using close_fn = int (*)(int);
using main_fn = int (*)(int, char**, char**);

accept_fn real_accept;
accept4_fn real_accept4;
read_fn real_read;
write_fn real_write;
send_fn real_send;
writev_fn real_writev;
sendmsg_fn real_sendmsg;
close_fn real_close;
main_fn real_main;

int proxy_fd = -1;                    // UDS to the driver daemon
bool spec_mode = true;                // RP_SPEC != "0"
pthread_mutex_t send_mu = PTHREAD_MUTEX_INITIALIZER;  // write serialization
constexpr int kMaxFd = 65536;
unsigned char tracked[kMaxFd];        // fds that arrived through accept()
unsigned char severed[kMaxFd];        // negative-acked: drop held output
uint32_t fd_gen[kMaxFd];              // bumps on real close (reuse guard)

// ---- outstanding-event ring (ack bookkeeping) ----------------------------
//
// Every forwarded event claims one monotone 64-bit seq; the ring slot at
// seq % kRing tracks its ack. The FRONTIER is the largest seq such that
// every seq <= it is acked — held replies whose watermark is <= the
// frontier are releasable (all input the app had consumed when the reply
// was produced has committed). The wire carries the low 32 seq bits;
// outstanding count < kRing << 2^32, so slot.seq disambiguates.

constexpr uint32_t kRing = 1 << 15;   // max outstanding events
enum SlotState : uint8_t { FREE = 0, SENT = 1, DONE = 2 };
struct AckSlot {
  uint64_t seq;
  int32_t status;
  SlotState state;
  int32_t fd;                         // tracked fd (sever on negative ack)
  uint32_t gen;
  bool waited;                        // a sync caller will consume status
};
AckSlot ring[kRing];
pthread_mutex_t resp_mu = PTHREAD_MUTEX_INITIALIZER;
pthread_cond_t resp_cv = PTHREAD_COND_INITIALIZER;
uint64_t next_seq = 1;
uint64_t frontier = 0;                // all seqs <= frontier are acked
uint64_t last_sent = 0;               // last seq claimed (any op)
bool driver_dead = false;

// ---- held output (speculative mode) --------------------------------------

struct OutChunk {
  int32_t fd;
  uint32_t gen;
  uint64_t watermark;                 // flush once frontier >= watermark
  bool is_close;                      // real_close(fd) instead of write
  std::string data;
};
std::deque<OutChunk>* outq;           // FIFO; watermarks are monotone
size_t outq_bytes = 0;
bool flushing = false;                // exactly one flusher at a time
constexpr size_t kOutCap = 64u << 20; // writer backpressure bound

void resolve() {
  real_accept = (accept_fn)dlsym(RTLD_NEXT, "accept");
  real_accept4 = (accept4_fn)dlsym(RTLD_NEXT, "accept4");
  real_read = (read_fn)dlsym(RTLD_NEXT, "read");
  real_write = (write_fn)dlsym(RTLD_NEXT, "write");
  real_send = (send_fn)dlsym(RTLD_NEXT, "send");
  real_writev = (writev_fn)dlsym(RTLD_NEXT, "writev");
  real_sendmsg = (sendmsg_fn)dlsym(RTLD_NEXT, "sendmsg");
  real_close = (close_fn)dlsym(RTLD_NEXT, "close");
}

bool io_exact(int fd, void* buf, size_t n, bool writing) {
  size_t done = 0;
  while (done < n) {
    ssize_t r = writing
        ? real_write(fd, static_cast<char*>(buf) + done, n - done)
        : real_read(fd, static_cast<char*>(buf) + done, n - done);
    if (r < 0 && errno == EINTR) continue;  // signals during the commit
                                            // wait must not kill the link
    if (r <= 0) return false;
    done += static_cast<size_t>(r);
  }
  return true;
}

// Write held bytes to the app's client socket. Blocking (the fd is the
// app's; a pathologically slow client stalls the flusher and thus all
// held output — global backpressure, the same failure mode as the
// reference leader writing replies synchronously from the app thread).
void flush_write(int fd, const std::string& data) {
  size_t done = 0;
  while (done < data.size()) {
    // MSG_NOSIGNAL: a vanished client must not SIGPIPE the flusher
    ssize_t r = real_send(fd, data.data() + done, data.size() - done,
                          MSG_NOSIGNAL);
    if (r < 0 && errno == EINTR) continue;
    if (r < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) {
      // event-loop apps set client fds O_NONBLOCK: a full socket
      // buffer is backpressure, not death — wait for drainage
      struct pollfd p;
      p.fd = fd;
      p.events = POLLOUT;
      if (poll(&p, 1, 5000) <= 0) return;  // stuck client: drop
      continue;
    }
    if (r <= 0) return;               // client died: drop the remainder
    done += static_cast<size_t>(r);
  }
}

// Release every held chunk whose watermark the frontier now covers.
// Called with resp_mu held; drops the lock across the actual writes
// (the socket write must not serialize ack processing). The `flushing`
// flag keeps exactly one active flusher — two threads draining the
// queue concurrently could reorder same-fd replies — and gates the
// hold_output fast path for the same reason.
void flush_outq_locked() {
  if (flushing) return;               // the active flusher will pick up
  flushing = true;
  while (outq && !outq->empty() && outq->front().watermark <= frontier) {
    OutChunk c = std::move(outq->front());
    outq->pop_front();
    outq_bytes -= c.data.size();
    // gen mismatch => the fd number was really closed (and possibly
    // reused by a NEW connection) since this chunk was queued: skip it
    // entirely — data must never leak to a different client, and a
    // stale close chunk's fd is no longer ours to close. The gen bump
    // for a deferred close happens HERE, under resp_mu, so the reader
    // thread's generation checks can never race it.
    bool gen_ok = c.fd >= 0 && c.fd < kMaxFd && fd_gen[c.fd] == c.gen;
    bool do_close = gen_ok && c.is_close;
    bool do_write = gen_ok && !c.is_close && !severed[c.fd];
    if (do_close) fd_gen[c.fd]++;     // deferred real close (severed or not)
    pthread_cond_broadcast(&resp_cv);   // space freed for blocked writers
    pthread_mutex_unlock(&resp_mu);
    if (do_close) real_close(c.fd);
    else if (do_write) flush_write(c.fd, c.data);
    pthread_mutex_lock(&resp_mu);
  }
  flushing = false;
}

// Advance the frontier over contiguous DONE slots, freeing them; then
// flush newly releasable held output. resp_mu held.
void advance_frontier_locked() {
  bool moved = false;
  for (;;) {
    AckSlot& s = ring[(frontier + 1) % kRing];
    if (s.state != DONE || s.seq != frontier + 1 || s.waited) break;
    s.state = FREE;
    frontier++;
    moved = true;
  }
  if (moved) {
    pthread_cond_broadcast(&resp_cv);
    flush_outq_locked();
  }
}

// Driver death — the SPECULATIVE output-commit discipline's hard case.
// Replies held for input the dead driver never acked must NOT be
// released: the input may never have committed, and releasing the
// reply fabricates an ack for a write that is lost (the client would
// hold an +OK for data no surviving replica has). So: flush only the
// chunks the PRE-DEATH commit frontier already covers (their input
// committed — releasing them is correct and avoids spurious client
// retries), DROP the speculative remainder, and sever every tracked
// connection so clients observe a reset — they retry against the new
// leader/world, exactly as on a refused event. The app itself has
// executed uncommitted input (diverged); its supervisor replaces it
// with a store-rebuilt instance at the next generation. resp_mu held.
void driver_death_locked() {
  driver_dead = true;
  proxy_fd = -1;
  flush_outq_locked();                // committed-covered chunks only
  if (outq) {
    // speculative data replies are DROPPED; deferred is_close chunks
    // must still run their real close (the fd was handed to us by the
    // app's close() — dropping the chunk would leak it open with the
    // client hanging instead of reset)
    while (!outq->empty()) {
      OutChunk c = std::move(outq->front());
      outq->pop_front();
      outq_bytes -= c.data.size();
      bool gen_ok = c.fd >= 0 && c.fd < kMaxFd && fd_gen[c.fd] == c.gen;
      if (gen_ok && c.is_close) {
        fd_gen[c.fd]++;
        real_close(c.fd);
      }
    }
  }
  for (int fd = 0; fd < kMaxFd; fd++) {
    if (tracked[fd]) {
      severed[fd] = 1;
      tracked[fd] = 0;
      shutdown(fd, SHUT_RDWR);
    }
  }
  pthread_cond_broadcast(&resp_cv);
}

// Reader thread: distributes seq-tagged responses. EOF / error => the
// driver died: every waiter is released with a refusal and all tracked
// connections sever (see driver_death_locked — held speculative output
// is dropped, never flushed).
void* reader_main(void*) {
  for (;;) {
    uint8_t buf[8];
    if (!io_exact(proxy_fd, buf, sizeof buf, false)) break;
    uint32_t wseq;
    int32_t status;
    memcpy(&wseq, buf, 4);
    memcpy(&status, buf + 4, 4);
    pthread_mutex_lock(&resp_mu);
    // the slot index depends only on the low bits of the 64-bit seq,
    // which equal the low bits of the wire seq
    AckSlot& s = ring[wseq % kRing];
    if (s.state == SENT && (uint32_t)s.seq == wseq) {
      s.status = status;
      s.state = DONE;
      if (status < 0 && s.fd >= 0 && s.fd < kMaxFd &&
          fd_gen[s.fd] == s.gen) {
        // the driver refused this event (leadership lost): the bytes
        // must never be acked to the client — sever the connection and
        // drop its held output so the client retries elsewhere
        severed[s.fd] = 1;
        tracked[s.fd] = 0;
        shutdown(s.fd, SHUT_RDWR);
      }
      if (s.waited)
        pthread_cond_broadcast(&resp_cv);   // sync caller consumes it
      else
        advance_frontier_locked();
    }
    pthread_mutex_unlock(&resp_mu);
  }
  pthread_mutex_lock(&resp_mu);
  driver_death_locked();
  pthread_mutex_unlock(&resp_mu);
  return nullptr;
}

// Claim a seq + ring slot (resp_mu held). Waits if the ring is full.
// Returns 0 on driver death.
uint64_t claim_slot_locked(int32_t fd, bool waited) {
  for (;;) {
    if (driver_dead) return 0;
    AckSlot& s = ring[next_seq % kRing];
    if (s.state == FREE) break;
    pthread_cond_wait(&resp_cv, &resp_mu);  // ring full: wait for acks
  }
  uint64_t seq = next_seq++;
  AckSlot& s = ring[seq % kRing];
  s.seq = seq;
  s.status = 0;
  s.state = SENT;
  s.fd = fd;
  s.gen = (fd >= 0 && fd < kMaxFd) ? fd_gen[fd] : 0;
  s.waited = waited;
  last_sent = seq;
  return seq;
}

bool send_event(uint64_t seq, uint8_t op, int32_t fd, const void* data,
                uint32_t len) {
  uint8_t hdr[13];
  uint32_t wseq = (uint32_t)seq;
  hdr[0] = op;
  memcpy(hdr + 1, &wseq, 4);
  memcpy(hdr + 5, &fd, 4);
  memcpy(hdr + 9, &len, 4);
  pthread_mutex_lock(&send_mu);       // short: enqueue order only
  int pfd = proxy_fd;
  bool ok = pfd >= 0 && io_exact(pfd, hdr, sizeof hdr, true) &&
            (len == 0 ||
             io_exact(pfd, const_cast<void*>(data), len, true));
  pthread_mutex_unlock(&send_mu);
  return ok;
}

// Synchronous event: send and wait for the driver's verdict (CONNECT
// always; SEND/CLOSE in sync mode). Other threads' events proceed
// concurrently (per-thread slots).
int32_t proxy_call(uint8_t op, int32_t fd, const void* data, uint32_t len) {
  if (proxy_fd < 0) return 0;
  pthread_mutex_lock(&resp_mu);
  uint64_t seq = claim_slot_locked(fd, /*waited=*/true);
  if (seq == 0) {
    pthread_mutex_unlock(&resp_mu);
    return 0;
  }
  pthread_mutex_unlock(&resp_mu);

  bool ok = send_event(seq, op, fd, data, len);

  pthread_mutex_lock(&resp_mu);
  AckSlot& s = ring[seq % kRing];
  if (!ok) driver_dead = true;
  while (s.state != DONE && !driver_dead)
    pthread_cond_wait(&resp_cv, &resp_mu);
  // death => REFUSE (the event's fate is unknown; the caller severs the
  // connection so the client retries elsewhere — never a silent
  // unreplicated pass-through)
  int32_t status = driver_dead ? -1 : s.status;
  s.waited = false;                   // frontier may now pass this slot
  if (s.state != DONE) s.state = DONE;
  advance_frontier_locked();
  if (driver_dead) driver_death_locked();
  pthread_mutex_unlock(&resp_mu);
  return status;
}

// Asynchronous event (speculative mode SEND/CLOSE): forward and return.
// The ack is consumed by the reader thread; ordering/visibility is
// enforced at output time via the frontier.
void proxy_cast(uint8_t op, int32_t fd, const void* data, uint32_t len) {
  if (proxy_fd < 0) return;
  pthread_mutex_lock(&resp_mu);
  uint64_t seq = claim_slot_locked(fd, /*waited=*/false);
  pthread_mutex_unlock(&resp_mu);
  if (seq == 0) return;
  if (!send_event(seq, op, fd, data, len)) {
    pthread_mutex_lock(&resp_mu);
    driver_death_locked();
    pthread_mutex_unlock(&resp_mu);
  }
}

// Hold (or pass) app output on a tracked fd. Returns the byte count the
// app should believe it wrote. `flags` carries the caller's send()
// flags for the pass-through path (MSG_NOSIGNAL always added: the app
// may rely on it rather than ignoring SIGPIPE process-wide; a tracked
// fd is always a socket, so real_send is valid even for write()).
ssize_t hold_output(int fd, const void* buf, size_t count, int flags) {
  pthread_mutex_lock(&resp_mu);
  if (severed[fd]) {
    pthread_mutex_unlock(&resp_mu);
    errno = ECONNRESET;
    return -1;
  }
  // fast path: nothing speculative outstanding, nothing queued, and no
  // flusher mid-write — the reply depends only on committed input and
  // cannot overtake a held one, so write straight through
  if ((!outq || outq->empty()) && frontier >= last_sent && !flushing) {
    pthread_mutex_unlock(&resp_mu);
    return real_send(fd, buf, count, flags | MSG_NOSIGNAL);
  }
  while (outq_bytes > kOutCap && !driver_dead)
    pthread_cond_wait(&resp_cv, &resp_mu);  // backpressure the app
  if (driver_dead) {
    // a tracked fd only reaches here by racing the death handler,
    // which severed it — this reply's input may never have committed,
    // so it must NOT reach the client
    pthread_mutex_unlock(&resp_mu);
    errno = ECONNRESET;
    return -1;
  }
  if (!outq) outq = new std::deque<OutChunk>();
  OutChunk c;
  c.fd = fd;
  c.gen = fd_gen[fd];
  c.watermark = last_sent;
  c.is_close = false;
  c.data.assign(static_cast<const char*>(buf), count);
  outq_bytes += count;
  outq->push_back(std::move(c));
  pthread_mutex_unlock(&resp_mu);
  return (ssize_t)count;
}

void rp_init() {
  resolve();
  const char* path = getenv("RP_PROXY_SOCK");
  if (!path) return;
  const char* spec = getenv("RP_SPEC");
  spec_mode = !(spec && spec[0] == '0');
  int fd = socket(AF_UNIX, SOCK_STREAM, 0);
  if (fd < 0) return;
  struct sockaddr_un addr;
  memset(&addr, 0, sizeof addr);
  addr.sun_family = AF_UNIX;
  strncpy(addr.sun_path, path, sizeof(addr.sun_path) - 1);
  if (connect(fd, reinterpret_cast<struct sockaddr*>(&addr),
              sizeof addr) != 0) {
    real_close(fd);
    return;
  }
  proxy_fd = fd;
  pthread_t thr;
  if (pthread_create(&thr, nullptr, reader_main, nullptr) != 0) {
    real_close(fd);
    proxy_fd = -1;
    return;
  }
  pthread_detach(thr);
  uint8_t flags = spec_mode ? 1 : 0;
  int32_t pid = static_cast<int32_t>(getpid());
  proxy_call(OP_HELLO, pid, &flags, 1);
}

bool is_socket(int fd) {
  struct stat st;
  return fstat(fd, &st) == 0 && S_ISSOCK(st.st_mode);
}

void on_accepted(int fd) {
  if (fd >= 0 && fd < kMaxFd && is_socket(fd)) {
    tracked[fd] = 1;
    severed[fd] = 0;
    // CONNECT carries the peer's address so the driver can tell its own
    // replay connections apart from real clients.
    uint8_t info[6] = {0, 0, 0, 0, 0, 0};
    struct sockaddr_in sa;
    socklen_t sl = sizeof sa;
    if (getpeername(fd, reinterpret_cast<struct sockaddr*>(&sa), &sl) == 0 &&
        sa.sin_family == AF_INET) {
      memcpy(info, &sa.sin_addr.s_addr, 4);
      memcpy(info + 4, &sa.sin_port, 2);  // network byte order
    }
    int32_t verdict = proxy_call(OP_CONNECT, fd, info, 6);
    if (verdict < 0) {
      // driver refused the connection (e.g. replicated session on a
      // deposed leader): sever it so the client reconnects elsewhere
      tracked[fd] = 0;
      shutdown(fd, SHUT_RDWR);
    } else if (verdict == 1) {
      tracked[fd] = 0;                // the driver's own: forget it
    }
  }
}

int wrapped_main(int argc, char** argv, char** envp) {
  rp_init();
  return real_main(argc, argv, envp);
}

}  // namespace

extern "C" {

int __libc_start_main(main_fn main, int argc, char** ubp_av,
                      void (*init)(void), void (*fini)(void),
                      void (*rtld_fini)(void), void* stack_end) {
  real_main = main;
  auto real = (int (*)(main_fn, int, char**, void (*)(void), void (*)(void),
                       void (*)(void), void*))
      dlsym(RTLD_NEXT, "__libc_start_main");
  return real(wrapped_main, argc, ubp_av, init, fini, rtld_fini, stack_end);
}

int accept(int sockfd, struct sockaddr* addr, socklen_t* addrlen) {
  if (!real_accept) resolve();
  int fd = real_accept(sockfd, addr, addrlen);
  if (proxy_fd >= 0) on_accepted(fd);
  // post-death quarantine: the speculative app has executed input that
  // never committed — NEW sessions must not be served from its
  // diverged state either (they get a reset and retry elsewhere)
  else if (driver_dead && fd >= 0) shutdown(fd, SHUT_RDWR);
  return fd;
}

int accept4(int sockfd, struct sockaddr* addr, socklen_t* addrlen,
            int flags) {
  if (!real_accept4) resolve();
  int fd = real_accept4(sockfd, addr, addrlen, flags);
  if (proxy_fd >= 0) on_accepted(fd);
  else if (driver_dead && fd >= 0) shutdown(fd, SHUT_RDWR);
  return fd;
}

ssize_t read(int fd, void* buf, size_t count) {
  if (!real_read) resolve();
  ssize_t n = real_read(fd, buf, count);
  // Replicate inbound client bytes. SYNC: block until the driver acks
  // (ack == committed on the leader); a negative status means the event
  // could NOT be committed (e.g. leadership was lost mid-session): the
  // bytes must never reach the app, so the connection is severed and
  // the client retries against the new leader. SPECULATIVE: forward and
  // return — the app executes immediately; its replies are held until
  // the commit frontier covers this event (output commit), and a late
  // negative ack severs the fd from the reader thread.
  if (n > 0 && proxy_fd >= 0 && fd >= 0 && fd < kMaxFd && tracked[fd]) {
    if (spec_mode) {
      proxy_cast(OP_SEND, fd, buf, static_cast<uint32_t>(n));
    } else if (proxy_call(OP_SEND, fd, buf,
                          static_cast<uint32_t>(n)) < 0) {
      tracked[fd] = 0;
      shutdown(fd, SHUT_RDWR);
      errno = ECONNRESET;
      return -1;
    }
  }
  return n;
}

ssize_t write(int fd, const void* buf, size_t count) {
  if (!real_write) resolve();
  if (spec_mode && proxy_fd >= 0 && fd >= 0 && fd < kMaxFd && tracked[fd])
    return hold_output(fd, buf, count, 0);
  return real_write(fd, buf, count);
}

ssize_t send(int sockfd, const void* buf, size_t len, int flags) {
  if (!real_send) resolve();
  if (spec_mode && proxy_fd >= 0 && sockfd >= 0 && sockfd < kMaxFd &&
      tracked[sockfd])
    return hold_output(sockfd, buf, len, flags);
  return real_send(sockfd, buf, len, flags);
}

ssize_t writev(int fd, const struct iovec* iov, int iovcnt) {
  if (!real_writev) resolve();
  if (spec_mode && proxy_fd >= 0 && fd >= 0 && fd < kMaxFd && tracked[fd]) {
    ssize_t total = 0;
    for (int i = 0; i < iovcnt; i++) {
      if (iov[i].iov_len == 0) continue;
      ssize_t r = hold_output(fd, iov[i].iov_base, iov[i].iov_len, 0);
      if (r < 0) return total > 0 ? total : r;
      total += r;
    }
    return total;
  }
  return real_writev(fd, iov, iovcnt);
}

ssize_t sendmsg(int sockfd, const struct msghdr* msg, int flags) {
  if (!real_sendmsg) resolve();
  if (spec_mode && proxy_fd >= 0 && sockfd >= 0 && sockfd < kMaxFd &&
      tracked[sockfd]) {
    ssize_t total = 0;
    for (size_t i = 0; i < msg->msg_iovlen; i++) {
      if (msg->msg_iov[i].iov_len == 0) continue;
      ssize_t r = hold_output(sockfd, msg->msg_iov[i].iov_base,
                              msg->msg_iov[i].iov_len, flags);
      if (r < 0) return total > 0 ? total : r;
      total += r;
    }
    return total;
  }
  return real_sendmsg(sockfd, msg, flags);
}

int close(int fd) {
  if (!real_close) resolve();
  if (proxy_fd >= 0 && fd >= 0 && fd < kMaxFd && tracked[fd]) {
    tracked[fd] = 0;
    if (spec_mode) {
      // the CLOSE is sequenced after this fd's pending input, and the
      // real close is deferred behind any held replies (a reply must
      // reach the client before its connection is torn down); the fd
      // number stays open until then, so the kernel cannot reuse it
      proxy_cast(OP_CLOSE, fd, nullptr, 0);
      pthread_mutex_lock(&resp_mu);
      // defer also while a flusher is mid-write: it may be blocked
      // inside the last popped chunk for THIS fd with resp_mu dropped —
      // closing now would truncate that reply (or race an fd reuse)
      bool defer = ((outq && !outq->empty()) || flushing) && !driver_dead;
      if (defer) {
        if (!outq) outq = new std::deque<OutChunk>();
        OutChunk c;
        c.fd = fd;
        c.gen = fd_gen[fd];
        c.watermark = last_sent;
        c.is_close = true;
        outq->push_back(std::move(c));
      } else {
        fd_gen[fd]++;
      }
      pthread_mutex_unlock(&resp_mu);
      if (defer) return 0;
      return real_close(fd);
    }
    proxy_call(OP_CLOSE, fd, nullptr, 0);
  }
  // any real close invalidates pending held chunks for this fd NUMBER —
  // the kernel may hand it to the next accepted connection immediately
  // (e.g. an fd severed by a negative ack is closed by the app on this
  // untracked path)
  if (fd >= 0 && fd < kMaxFd) {
    pthread_mutex_lock(&resp_mu);
    fd_gen[fd]++;
    pthread_mutex_unlock(&resp_mu);
  }
  return real_close(fd);
}

}  // extern "C"
