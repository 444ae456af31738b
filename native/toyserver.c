/* toyserver — a deliberately unmodified, plain-libc TCP key-value server.
 *
 * Plays the role of the reference's pristine Redis/memcached builds
 * (apps/redis/mk): the e2e tests replicate it via LD_PRELOAD=interpose.so
 * without it knowing. Protocol (newline-framed, one request per line):
 *   SET <key> <value>\n  -> +OK\n
 *   GET <key>\n          -> <value>\n or -\n
 *   DEL <key>\n          -> +OK\n
 *   COUNT\n              -> <n>\n
 * Uses accept()/read()/write()/close() directly — the exact syscall
 * surface the shim hooks. Two serving modes:
 *   toyserver <port>      poll-based single thread (redis-style)
 *   toyserver <port> -t   thread-per-connection (memcached-style) — many
 *                         reads block in the shim's commit wait
 *                         concurrently, exercising its pipelining
 */
#include <arpa/inet.h>
#include <errno.h>
#include <netinet/in.h>
#include <poll.h>
#include <pthread.h>
#include <signal.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include <sys/socket.h>
#include <unistd.h>

#define MAXKV 131072            /* open-addressing table, power of two */
#define MAXC 64
#define BUFSZ 65536

/* Open-addressing hash KVS (linear probing, tombstone-free deletes by
 * backward-shift) so benchmark-scale key counts stay O(1) per op. */
static char keys[MAXKV][64], vals[MAXKV][256];
static unsigned char used[MAXKV];
static int nkv = 0;

static unsigned kv_hash(const char* k) {
  unsigned h = 2166136261u;
  while (*k) h = (h ^ (unsigned char)*k++) * 16777619u;
  return h & (MAXKV - 1);
}
static int kv_find(const char* k) {      /* slot of key, or -1 */
  for (unsigned i = kv_hash(k), n = 0; n < MAXKV;
       i = (i + 1) & (MAXKV - 1), n++) {
    if (!used[i]) return -1;
    if (!strcmp(keys[i], k)) return (int)i;
  }
  return -1;
}
static const char* kv_get(const char* k) {
  int i = kv_find(k);
  return i < 0 ? NULL : vals[i];
}
static void kv_set(const char* k, const char* v) {
  for (unsigned i = kv_hash(k), n = 0; n < MAXKV;
       i = (i + 1) & (MAXKV - 1), n++) {
    if (used[i] && !strcmp(keys[i], k)) {
      snprintf(vals[i], 256, "%s", v);
      return;
    }
    if (!used[i]) {
      if (nkv >= MAXKV - 1) return;      /* table full: drop */
      used[i] = 1;
      snprintf(keys[i], 64, "%s", k);
      snprintf(vals[i], 256, "%s", v);
      nkv++;
      return;
    }
  }
}
static void kv_del(const char* k) {
  int i = kv_find(k);
  if (i < 0) return;
  used[i] = 0;
  nkv--;
  /* re-insert the probe chain after the hole */
  for (unsigned j = (i + 1) & (MAXKV - 1); used[j];
       j = (j + 1) & (MAXKV - 1)) {
    used[j] = 0;
    nkv--;
    char kk[64], vv[256];
    memcpy(kk, keys[j], 64);
    memcpy(vv, vals[j], 256);
    kv_set(kk, vv);
  }
}

struct conn { int fd; char buf[BUFSZ]; int len; };

static pthread_mutex_t kv_mu = PTHREAD_MUTEX_INITIALIZER;

static void handle_line(int fd, char* line) {
  char out[512], k[64], v[256];
  pthread_mutex_lock(&kv_mu);
  if (sscanf(line, "SET %63s %255[^\n]", k, v) == 2) {
    kv_set(k, v);
    snprintf(out, sizeof out, "+OK\n");
  } else if (sscanf(line, "GET %63s", k) == 1) {
    const char* r = kv_get(k);
    snprintf(out, sizeof out, "%s\n", r ? r : "-");
  } else if (sscanf(line, "DEL %63s", k) == 1) {
    kv_del(k);
    snprintf(out, sizeof out, "+OK\n");
  } else if (sscanf(line, "ECHO %255s", v) == 1) {
    /* request/response no-op: the reply embeds the caller's token, so a
     * barrier probe can identify its own response among buffered
     * replies to earlier pipelined commands */
    snprintf(out, sizeof out, "=%s\n", v);
  } else if (!strncmp(line, "COUNT", 5)) {
    snprintf(out, sizeof out, "%d\n", nkv);
  } else if (!strncmp(line, "DUMPALL", 7)) {
    /* full-state listing: "<key> <value>\n" per pair, "." terminator —
     * the app-level snapshot hook bounded recovery uses (the analog of
     * redis BGSAVE producing an RDB: app state without event history) */
    for (unsigned i = 0; i < MAXKV; i++) {
      if (!used[i]) continue;
      char lineb[512];
      int ln = snprintf(lineb, sizeof lineb, "%s %s\n", keys[i], vals[i]);
      ssize_t w0 = write(fd, lineb, (size_t)ln);
      (void)w0;
    }
    snprintf(out, sizeof out, ".\n");
  } else {
    snprintf(out, sizeof out, "-ERR\n");
  }
  pthread_mutex_unlock(&kv_mu);
  ssize_t w = write(fd, out, strlen(out));
  (void)w;
}

/* ---- thread-per-connection mode ---- */
static void* conn_main(void* arg) {
  struct conn* c = (struct conn*)arg;
  c->len = 0;
  for (;;) {
    ssize_t n = read(c->fd, c->buf + c->len, (size_t)(BUFSZ - c->len - 1));
    if (n <= 0) break;
    c->len += (int)n;
    c->buf[c->len] = 0;
    char* start = c->buf;
    char* nl;
    while ((nl = strchr(start, '\n'))) {
      *nl = 0;
      handle_line(c->fd, start);
      start = nl + 1;
    }
    int rest = (int)(c->buf + c->len - start);
    memmove(c->buf, start, (size_t)rest);
    c->len = rest;
  }
  close(c->fd);
  free(c);
  return NULL;
}

int main(int argc, char** argv) {
  int port = argc > 1 ? atoi(argv[1]) : 7000;
  int threaded = argc > 2 && !strcmp(argv[2], "-t");
  /* like the servers this stands in for (redis.c setupSignalHandlers,
   * memcached sigignore): a reply written to a connection the peer — or
   * the shim's sever of a refused session — already shut down is an
   * EPIPE to skip, not a signal that kills the whole store */
  signal(SIGPIPE, SIG_IGN);
  int ls = socket(AF_INET, SOCK_STREAM, 0);
  int one = 1;
  setsockopt(ls, SOL_SOCKET, SO_REUSEADDR, &one, sizeof one);
  struct sockaddr_in a = {0};
  a.sin_family = AF_INET;
  a.sin_addr.s_addr = htonl(INADDR_LOOPBACK);
  a.sin_port = htons((unsigned short)port);
  if (bind(ls, (struct sockaddr*)&a, sizeof a) != 0) { perror("bind"); return 1; }
  listen(ls, 64);
  fprintf(stderr, "toyserver listening on %d%s\n", port,
          threaded ? " (threaded)" : "");

  if (threaded) {
    for (;;) {
      int fd = accept(ls, NULL, NULL);
      if (fd < 0) continue;
      struct conn* c = (struct conn*)malloc(sizeof *c);
      if (!c) { close(fd); continue; }
      c->fd = fd;
      pthread_t thr;
      if (pthread_create(&thr, NULL, conn_main, c) != 0) {
        close(fd);
        free(c);
        continue;
      }
      pthread_detach(thr);
    }
  }

  struct conn cs[MAXC];
  for (int i = 0; i < MAXC; i++) cs[i].fd = -1;

  for (;;) {
    struct pollfd pfds[MAXC + 1];
    int idx[MAXC + 1], np = 0;
    pfds[np].fd = ls; pfds[np].events = POLLIN; idx[np++] = -1;
    for (int i = 0; i < MAXC; i++)
      if (cs[i].fd >= 0) {
        pfds[np].fd = cs[i].fd; pfds[np].events = POLLIN; idx[np++] = i;
      }
    if (poll(pfds, (nfds_t)np, -1) < 0) continue;
    for (int p = 0; p < np; p++) {
      if (!(pfds[p].revents & (POLLIN | POLLHUP | POLLERR))) continue;
      if (idx[p] < 0) {
        int fd = accept(ls, NULL, NULL);
        if (fd < 0) continue;
        int i;
        for (i = 0; i < MAXC && cs[i].fd >= 0; i++) {}
        if (i == MAXC) { close(fd); continue; }
        cs[i].fd = fd; cs[i].len = 0;
      } else {
        struct conn* c = &cs[idx[p]];
        ssize_t n = read(c->fd, c->buf + c->len,
                         (size_t)(BUFSZ - c->len - 1));
        if (n <= 0) { close(c->fd); c->fd = -1; continue; }
        c->len += (int)n;
        c->buf[c->len] = 0;
        char* start = c->buf;
        char* nl;
        while ((nl = strchr(start, '\n'))) {
          *nl = 0;
          handle_line(c->fd, start);
          start = nl + 1;
        }
        int rest = (int)(c->buf + c->len - start);
        memmove(c->buf, start, (size_t)rest);
        c->len = rest;
      }
    }
  }
}
