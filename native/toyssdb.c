/* toyssdb — a plain-libc TCP key-value server with batched writes.
 *
 * Plays the role of the reference's pristine SSDB build (apps/ssdb/mk)
 * as toyserver plays Redis's: replicated via LD_PRELOAD=interpose.so
 * without knowing. toyserver's poll-mode server and line protocol
 * (newline-framed, one request per line), plus SSDB's multi_set:
 *   SET <key> <value>\n  -> +OK\n
 *   GET <key>\n          -> <value>\n or -\n
 *   DEL <key>\n          -> +OK\n
 *   COUNT\n              -> <n>\n
 *   multi_set <key> <value> [<key> <value> ...]\n -> +OK <pairs>\n
 * A multi_set is stored whole or not at all: a malformed line (an odd
 * number of words, a key over MAXKEY or a value over MAXVAL bytes, more
 * than MAXPAIRS pairs) or a table without room for every pair answers
 * -ERR and stores nothing. Keys and values hold no space.
 *
 * The table holds MAXREC records (2^21: a benchmark run of 16-pair
 * requests writes about a million keys that never repeat) behind an
 * index of 2 * MAXREC entries, so the load factor stays under 0.5; the
 * records lie in arrival order in one arena, so the memory touched
 * grows with the keys held and not with the table's size.
 */
#include <arpa/inet.h>
#include <netinet/in.h>
#include <poll.h>
#include <signal.h>
#include <stdint.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include <sys/socket.h>
#include <unistd.h>

#define MAXREC (1u << 21)       /* records held */
#define NIDX (MAXREC << 1)      /* index entries, power of two */
#define MAXKEY 23
#define MAXVAL 103
#define MAXPAIRS 64
#define MAXC 256                /* poll-mode connections */
#define BUFSZ 65536

struct rec { char key[MAXKEY + 1], val[MAXVAL + 1]; };   /* 128 bytes */
static struct rec recs[MAXREC];         /* 256 MB of BSS, touched as used */
static uint32_t idx[NIDX];              /* 0 empty, DEAD, else record + 1 */
static uint32_t nrec = 0, nlive = 0, free_head = 0;      /* record + 1 */
#define DEAD 0xffffffffu

static uint32_t kv_hash(const char* k) {
  uint32_t h = 2166136261u;
  while (*k) h = (h ^ (unsigned char)*k++) * 16777619u;
  return h & (NIDX - 1);
}
/* index position of the key, or -1 */
static long kv_find(const char* k) {
  for (uint32_t i = kv_hash(k), n = 0; n < NIDX;
       i = (i + 1) & (NIDX - 1), n++) {
    if (!idx[i]) return -1;
    if (idx[i] != DEAD && !strcmp(recs[idx[i] - 1].key, k)) return (long)i;
  }
  return -1;
}
static uint32_t kv_room(void) { return MAXREC - nlive; }
/* the caller has checked the lengths and that there is room */
static void kv_set(const char* k, const char* v) {
  long at = kv_find(k);
  if (at >= 0) {
    strcpy(recs[idx[at] - 1].val, v);
    return;
  }
  uint32_t r;
  if (free_head) {              /* a deleted record's place */
    r = free_head;
    memcpy(&free_head, recs[r - 1].val, sizeof free_head);
  } else {
    r = ++nrec;
  }
  strcpy(recs[r - 1].key, k);
  strcpy(recs[r - 1].val, v);
  uint32_t i = kv_hash(k);
  while (idx[i] && idx[i] != DEAD) i = (i + 1) & (NIDX - 1);
  idx[i] = r;
  nlive++;
}
static void kv_del(const char* k) {
  long at = kv_find(k);
  if (at < 0) return;
  uint32_t r = idx[at];
  idx[at] = DEAD;
  recs[r - 1].key[0] = 0;
  memcpy(recs[r - 1].val, &free_head, sizeof free_head);
  free_head = r;
  nlive--;
}

/* "<key> <value> <key> <value> ..." stored whole or not at all;
 * returns the reply */
static const char* multi_set(char* pairs, char* out, size_t cap) {
  char* tok[2 * MAXPAIRS];
  int nt = 0;
  char* save;
  for (char* t = strtok_r(pairs, " ", &save); t;
       t = strtok_r(NULL, " ", &save)) {
    if (nt == 2 * MAXPAIRS) return "-ERR too many pairs\n";
    tok[nt++] = t;
  }
  if (!nt || nt % 2) return "-ERR pairs\n";
  for (int t = 0; t < nt; t += 2)
    if (strlen(tok[t]) > MAXKEY || strlen(tok[t + 1]) > MAXVAL)
      return "-ERR too long\n";
  if ((uint32_t)(nt / 2) > kv_room()) return "-ERR full\n";
  for (int t = 0; t < nt; t += 2) kv_set(tok[t], tok[t + 1]);
  snprintf(out, cap, "+OK %d\n", nt / 2);
  return out;
}

static void handle_line(int fd, char* line) {
  char out[272], k[64], v[256];
  const char* reply = out;
  if (!strncmp(line, "multi_set ", 10)) {
    reply = multi_set(line + 10, out, sizeof out);
  } else if (sscanf(line, "SET %63s %255[^\n]", k, v) == 2) {
    if (strlen(k) > MAXKEY || strlen(v) > MAXVAL || strchr(v, ' ')) {
      reply = "-ERR too long\n";
    } else if (!kv_room() && kv_find(k) < 0) {
      reply = "-ERR full\n";
    } else {
      kv_set(k, v);
      reply = "+OK\n";
    }
  } else if (sscanf(line, "GET %63s", k) == 1) {
    long at = kv_find(k);
    snprintf(out, sizeof out, "%s\n", at < 0 ? "-" : recs[idx[at] - 1].val);
  } else if (sscanf(line, "DEL %63s", k) == 1) {
    kv_del(k);
    reply = "+OK\n";
  } else if (!strncmp(line, "COUNT", 5)) {
    snprintf(out, sizeof out, "%u\n", nlive);
  } else {
    reply = "-ERR\n";
  }
  ssize_t w = write(fd, reply, strlen(reply));
  (void)w;
}

struct conn { int fd; char buf[BUFSZ]; int len; };

int main(int argc, char** argv) {
  int port = argc > 1 ? atoi(argv[1]) : 8888;
  /* a reply written to a connection the peer, or the shim's sever of a
   * refused session, already shut down is an EPIPE to skip */
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
  fprintf(stderr, "toyssdb listening on %d\n", port);

  static struct conn cs[MAXC];     /* 16 MB: not on the stack */
  for (int i = 0; i < MAXC; i++) cs[i].fd = -1;

  for (;;) {
    struct pollfd pfds[MAXC + 1];
    int at[MAXC + 1], np = 0;
    pfds[np].fd = ls; pfds[np].events = POLLIN; at[np++] = -1;
    for (int i = 0; i < MAXC; i++)
      if (cs[i].fd >= 0) {
        pfds[np].fd = cs[i].fd; pfds[np].events = POLLIN; at[np++] = i;
      }
    if (poll(pfds, (nfds_t)np, -1) < 0) continue;
    for (int p = 0; p < np; p++) {
      if (!(pfds[p].revents & (POLLIN | POLLHUP | POLLERR))) continue;
      if (at[p] < 0) {
        int fd = accept(ls, NULL, NULL);
        if (fd < 0) continue;
        int i;
        for (i = 0; i < MAXC && cs[i].fd >= 0; i++) {}
        if (i == MAXC) { close(fd); continue; }
        cs[i].fd = fd; cs[i].len = 0;
      } else {
        struct conn* c = &cs[at[p]];
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
