/* toyserver — a deliberately unmodified, plain-libc TCP key-value server.
 *
 * Plays the role of the reference's pristine Redis/memcached builds
 * (apps/redis/mk): the e2e tests replicate it via LD_PRELOAD=interpose.so
 * without it knowing. Protocol (newline-framed, one request per line):
 *   SET <key> <value>\n  -> +OK\n (-ERR full when the table is)
 *   GET <key>\n          -> <value>\n or -\n
 *   DEL <key>\n          -> +OK\n
 *   COUNT\n              -> <n>\n (string keys and hash records)
 *   HMSET <key> <field> <value> [<field> <value> ...]\n -> +OK\n
 *   HGETALL <key>\n      -> <field> <value> [<field> <value> ...]\n or -\n
 * HMSET creates the record or updates the named fields (redis's hash
 * type, as YCSB's Redis binding uses it); HGETALL answers on ONE line,
 * the fields in the order they were first set. Values hold no space.
 * Uses accept()/read()/write()/close() directly — the exact syscall
 * surface the shim hooks. Two serving modes:
 *   toyserver <port>      poll-based single thread (redis-style)
 *   toyserver <port> -t   thread-per-connection (memcached-style) — many
 *                         reads block in the shim's commit wait
 *                         concurrently, exercising its pipelining
 * and two options, after the port, in any order with -t:
 *   -s <n>   the string table has 2^n slots (8..26; without it 2^17 =
 *            131,072, which holds 131,071 keys): 320 bytes a slot,
 *            allocated zeroed and every page of it touched at start,
 *            once the port listens (2^21 slots are 671 MB resident, as
 *            a store that has been loaded is: the keys hash all over
 *            the table, so a run would otherwise spend its first
 *            seconds taking a page fault or two a new key)
 *   -j       the answers to the requests of ONE read are joined into
 *            one write, as redis answers a pipelined batch (it adds
 *            replies to the client's buffer and sends it once an event
 *            loop turn). Without it a client that pipelines is
 *            answered a line at a time, each with a write of its own:
 *            by Nagle's algorithm the second answer waits for the
 *            client's ACK of the first, so such a client asks for its
 *            ACKs at once (TCP_QUICKACK) or waits 40 ms a batch. A
 *            read that holds one request is answered alike either way.
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

#define MAXKV 131072            /* open-addressing table, power of two:
                                 * the size without -s */
#define MAXC 256                /* poll-mode connections; a front-end of a
                                 * G-group deployment holds its clients' and
                                 * (G - 1) groups' replayed ones */
#define BUFSZ 65536

/* Open-addressing hash KVS (linear probing, tombstone-free deletes by
 * backward-shift) so benchmark-scale key counts stay O(1) per op. */
static char (*keys)[64], (*vals)[256];
static unsigned char* used;
static unsigned kv_cap = MAXKV;         /* slots: -s sets it */
static int nkv = 0;

static unsigned kv_hash(const char* k) {
  unsigned h = 2166136261u;
  while (*k) h = (h ^ (unsigned char)*k++) * 16777619u;
  return h & (kv_cap - 1);
}
static int kv_find(const char* k) {      /* slot of key, or -1 */
  for (unsigned i = kv_hash(k), n = 0; n < kv_cap;
       i = (i + 1) & (kv_cap - 1), n++) {
    if (!used[i]) return -1;
    if (!strcmp(keys[i], k)) return (int)i;
  }
  return -1;
}
static const char* kv_get(const char* k) {
  int i = kv_find(k);
  return i < 0 ? NULL : vals[i];
}
static int kv_set(const char* k, const char* v) {   /* 0, or -1: full */
  for (unsigned i = kv_hash(k), n = 0; n < kv_cap;
       i = (i + 1) & (kv_cap - 1), n++) {
    if (used[i] && !strcmp(keys[i], k)) {
      snprintf(vals[i], 256, "%s", v);
      return 0;
    }
    if (!used[i]) {
      if ((unsigned)nkv >= kv_cap - 1) return -1;
      used[i] = 1;
      snprintf(keys[i], 64, "%s", k);
      snprintf(vals[i], 256, "%s", v);
      nkv++;
      return 0;
    }
  }
  return -1;
}
static void kv_del(const char* k) {
  int i = kv_find(k);
  if (i < 0) return;
  used[i] = 0;
  nkv--;
  /* re-insert the probe chain after the hole */
  for (unsigned j = (i + 1) & (kv_cap - 1); used[j];
       j = (j + 1) & (kv_cap - 1)) {
    used[j] = 0;
    nkv--;
    char kk[64], vv[256];
    memcpy(kk, keys[j], 64);
    memcpy(vv, vals[j], 256);
    kv_set(kk, vv);
  }
}

/* Hash records (HMSET/HGETALL): a table of their own, same probing, no
 * delete. A record holds up to MAXF fields in the order first set. */
#define MAXH 16384              /* power of two */
#define MAXF 16
struct hrec {
  char key[64];
  int nf;
  char field[MAXF][32], val[MAXF][256];
};
static struct hrec hrecs[MAXH];
static unsigned char hused[MAXH];
static int nh = 0;

static struct hrec* h_find(const char* k, int create) {
  for (unsigned i = kv_hash(k) & (MAXH - 1), n = 0; n < MAXH;
       i = (i + 1) & (MAXH - 1), n++) {
    if (hused[i] && !strcmp(hrecs[i].key, k)) return &hrecs[i];
    if (!hused[i]) {
      if (!create || nh >= MAXH - 1) return NULL;
      hused[i] = 1;
      snprintf(hrecs[i].key, 64, "%s", k);
      hrecs[i].nf = 0;
      nh++;
      return &hrecs[i];
    }
  }
  return NULL;
}
/* "<field> <value> <field> <value> ..." into the record, all of it or
 * none; returns the reply */
static const char* h_mset(const char* k, char* pairs) {
  char* tok[2 * MAXF];
  int nt = 0;
  char* save;
  for (char* t = strtok_r(pairs, " ", &save); t;
       t = strtok_r(NULL, " ", &save)) {
    if (nt == 2 * MAXF) return "-ERR full\n";
    tok[nt++] = t;
  }
  if (!nt || nt % 2) return "-ERR\n";
  if (kv_find(k) >= 0) return "-ERR wrongtype\n";
  struct hrec* h = h_find(k, 0);
  int fresh = 0;                /* fields the record does not have yet */
  for (int t = 0; t < nt; t += 2) {
    int j, seen = 0;
    for (j = 0; h && j < h->nf && strcmp(h->field[j], tok[t]); j++) {}
    for (int u = 0; u < t && !seen; u += 2) seen = !strcmp(tok[u], tok[t]);
    fresh += !seen && (!h || j == h->nf);
  }
  if ((h ? h->nf : 0) + fresh > MAXF) return "-ERR full\n";
  if (!h && !(h = h_find(k, 1))) return "-ERR full\n";
  for (int t = 0; t < nt; t += 2) {
    int j;
    for (j = 0; j < h->nf && strcmp(h->field[j], tok[t]); j++) {}
    if (j == h->nf) snprintf(h->field[h->nf++], 32, "%s", tok[t]);
    snprintf(h->val[j], 256, "%s", tok[t + 1]);
  }
  return "+OK\n";
}

struct conn { int fd; char buf[BUFSZ]; int len; };

static pthread_mutex_t kv_mu = PTHREAD_MUTEX_INITIALIZER;

/* -j: what a read's requests have been answered so far, sent by
 * flush_replies() when the read's last line is done (or this is full) */
static int join_replies = 0;
static __thread char joined[BUFSZ];
static __thread int njoined = 0;

static void flush_replies(int fd) {
  if (!njoined) return;
  ssize_t w = write(fd, joined, (size_t)njoined);
  (void)w;
  njoined = 0;
}
static void reply(int fd, const char* out, size_t len) {
  if (join_replies) {               /* no reply is as long as this */
    if ((size_t)njoined + len > sizeof joined) flush_replies(fd);
    memcpy(joined + njoined, out, len);
    njoined += (int)len;
    return;
  }
  ssize_t w = write(fd, out, len);
  (void)w;
}

static void handle_line(int fd, char* line) {
  char out[MAXF * 290 + 8], k[64], v[256];
  int at = 0;
  pthread_mutex_lock(&kv_mu);
  if (sscanf(line, "SET %63s %255[^\n]", k, v) == 2) {
    snprintf(out, sizeof out, "%s",
             h_find(k, 0) ? "-ERR wrongtype\n"
             : kv_set(k, v) ? "-ERR full\n" : "+OK\n");
  } else if (sscanf(line, "HMSET %63s %n", k, &at) == 1 && at) {
    snprintf(out, sizeof out, "%s", h_mset(k, line + at));
  } else if (sscanf(line, "HGETALL %63s", k) == 1) {
    struct hrec* h = h_find(k, 0);
    int n = 0;
    for (int j = 0; h && j < h->nf; j++)
      n += snprintf(out + n, sizeof out - (size_t)n, "%s%s %s",
                    j ? " " : "", h->field[j], h->val[j]);
    snprintf(out + n, sizeof out - (size_t)n, "%s\n", h ? "" : "-");
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
    snprintf(out, sizeof out, "%d\n", nkv + nh);
  } else if (!strncmp(line, "DUMPALL", 7)) {
    /* full-state listing: "<key> <value>\n" per pair, "." terminator —
     * the app-level snapshot hook bounded recovery uses (the analog of
     * redis BGSAVE producing an RDB: app state without event history).
     * String keys ONLY: hash records are not listed, so an app that
     * holds them is not rebuilt from this listing */
    for (unsigned i = 0; i < kv_cap; i++) {
      if (!used[i]) continue;
      char lineb[512];
      int ln = snprintf(lineb, sizeof lineb, "%s %s\n", keys[i], vals[i]);
      reply(fd, lineb, (size_t)ln);
    }
    snprintf(out, sizeof out, ".\n");
  } else {
    snprintf(out, sizeof out, "-ERR\n");
  }
  pthread_mutex_unlock(&kv_mu);
  reply(fd, out, strlen(out));
}

/* the lines of one read, each answered in turn; what is left of an
 * unfinished one moves to the front */
static void handle_read(struct conn* c) {
  char* start = c->buf;
  char* nl;
  while ((nl = strchr(start, '\n'))) {
    *nl = 0;
    handle_line(c->fd, start);
    start = nl + 1;
  }
  flush_replies(c->fd);
  int rest = (int)(c->buf + c->len - start);
  memmove(c->buf, start, (size_t)rest);
  c->len = rest;
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
    handle_read(c);
  }
  close(c->fd);
  free(c);
  return NULL;
}

int main(int argc, char** argv) {
  int port = argc > 1 ? atoi(argv[1]) : 7000;
  int threaded = 0, sized = 0;
  for (int i = 2; i < argc; i++) {
    if (!strcmp(argv[i], "-t")) {
      threaded = 1;
    } else if (!strcmp(argv[i], "-j")) {
      join_replies = 1;
    } else if (!strcmp(argv[i], "-s") && i + 1 < argc) {
      int bits = atoi(argv[++i]);
      if (bits < 8 || bits > 26) {
        fprintf(stderr, "toyserver: -s %d: 8..26\n", bits);
        return 2;
      }
      kv_cap = 1u << bits;
      sized = 1;
    } else {
      fprintf(stderr, "usage: toyserver <port> [-t] [-j] [-s <bits>]\n");
      return 2;
    }
  }
  keys = calloc(kv_cap, sizeof *keys);
  vals = calloc(kv_cap, sizeof *vals);
  used = calloc(kv_cap, 1);
  if (!keys || !vals || !used) { perror("calloc"); return 1; }
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
  listen(ls, MAXC);
  if (sized)                    /* who connects meanwhile waits to be accepted */
    for (size_t i = 0; i < kv_cap; i += 4096 / sizeof *vals) {
      ((volatile char*)vals[i])[0] = 0;
      if (i % (4096 / sizeof *keys) == 0) ((volatile char*)keys[i])[0] = 0;
      if (i % 4096 == 0) ((volatile unsigned char*)used)[i] = 0;
    }
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

  static struct conn cs[MAXC];     /* 16 MB: not on the stack */
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
        handle_read(c);
      }
    }
  }
}
