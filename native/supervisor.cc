// tpujob native process supervisor.
//
// The compiled half of the runtime's kubelet analogue: spawn (fork/execve
// with setsid + log redirection), monitor (waitpid with a thread-safe
// completion registry), and kill (process-group signals with a
// grace-then-SIGKILL escalation). The Go reference delegates all of this to
// the kubelet and only *observes* container termination states
// (pkg/trainer/replicas.go:310-363, pkg/controller.v2/pod_control.go:54-165);
// on a bare TPU host this library IS the container runtime.
//
// Exit codes are normalized to the shell/k8s convention the exit-code
// taxonomy (pkg/util/train/train_util.go:18-53) is written against:
// 0-255 for normal exits, 128+signal for signal deaths (so SIGKILL -> 137,
// SIGTERM -> 143), never Python's negative-returncode convention.
//
// Thread model: any number of embedding-process threads may call any
// function on any pid concurrently. waitpid(2) reaps exactly once; the
// registry makes wait/poll idempotent afterwards (the losing racer reads
// the winner's recorded status).

#include <errno.h>
#include <fcntl.h>
#include <signal.h>
#include <stdint.h>
#include <string.h>
#include <sys/types.h>
#include <sys/wait.h>
#include <time.h>
#include <unistd.h>

#include <mutex>
#include <unordered_map>

namespace {

struct Entry {
  bool done = false;
  int code = 0;
};

std::mutex g_mu;
std::unordered_map<long, Entry> g_procs;

int normalize(int status) {
  if (WIFEXITED(status)) return WEXITSTATUS(status);
  if (WIFSIGNALED(status)) return 128 + WTERMSIG(status);
  return 255;  // stopped/continued can't reach here (no WUNTRACED)
}

int record(long pid, int status) {
  std::lock_guard<std::mutex> l(g_mu);
  Entry& e = g_procs[pid];
  e.done = true;
  e.code = normalize(status);
  return e.code;
}

// True while the registry holds a slot for pid, reaped or not.
bool tracked(long pid) {
  std::lock_guard<std::mutex> l(g_mu);
  return g_procs.count(pid) != 0;
}

bool lookup(long pid, int* code) {
  std::lock_guard<std::mutex> l(g_mu);
  auto it = g_procs.find(pid);
  if (it != g_procs.end() && it->second.done) {
    *code = it->second.code;
    return true;
  }
  return false;
}

void sleep_ms(long ms) {
  struct timespec ts;
  ts.tv_sec = ms / 1000;
  ts.tv_nsec = (ms % 1000) * 1000000L;
  nanosleep(&ts, nullptr);
}

}  // namespace

extern "C" {

// Spawn argv with envp. The child setsid()s (it owns a fresh process group,
// so supervisor signals never leak in and group kills take the whole
// subtree), redirects stdout+stderr to log_path when given (append mode —
// the kubelet-log analogue the dashboard serves), and chdir()s to workdir
// when given. Returns the pid, or -errno on failure — including exec
// failure, which is reported synchronously through a CLOEXEC pipe instead
// of surfacing as a mysterious exit-127 child.
long tpuj_spawn(const char* const* argv, const char* const* envp,
                const char* workdir, const char* log_path) {
  int logfd = -1;
  if (log_path && log_path[0]) {
    logfd = open(log_path, O_WRONLY | O_CREAT | O_APPEND, 0644);
    if (logfd < 0) return -(long)errno;
  }
  int ep[2];
  if (pipe2(ep, O_CLOEXEC) != 0) {
    int e = errno;
    if (logfd >= 0) close(logfd);
    return -(long)e;
  }
  pid_t pid = fork();
  if (pid < 0) {
    int e = errno;
    if (logfd >= 0) close(logfd);
    close(ep[0]);
    close(ep[1]);
    return -(long)e;
  }
  if (pid == 0) {
    // Child: async-signal-safe calls only until execve.
    setsid();
    if (logfd >= 0) {
      dup2(logfd, 1);
      dup2(logfd, 2);
      close(logfd);
    }
    if (workdir && workdir[0] && chdir(workdir) != 0) {
      int e = errno;
      ssize_t ignored = write(ep[1], &e, sizeof e);
      (void)ignored;
      _exit(127);
    }
    execve(argv[0], const_cast<char* const*>(argv),
           const_cast<char* const*>(envp));
    int e = errno;
    ssize_t ignored = write(ep[1], &e, sizeof e);
    (void)ignored;
    _exit(127);
  }
  if (logfd >= 0) close(logfd);
  close(ep[1]);
  int child_errno = 0;
  ssize_t n;
  do {
    n = read(ep[0], &child_errno, sizeof child_errno);
  } while (n < 0 && errno == EINTR);
  close(ep[0]);
  if (n > 0) {  // exec (or chdir) failed in the child
    int status;
    while (waitpid(pid, &status, 0) < 0 && errno == EINTR) {
    }
    return -(long)child_errno;
  }
  std::lock_guard<std::mutex> l(g_mu);
  g_procs.emplace((long)pid, Entry{});
  return (long)pid;
}

// Blocking wait. Returns the normalized exit code; idempotent (a second
// waiter — or a waiter racing tpuj_terminate — reads the recorded status).
// Returns -ECHILD for a pid this supervisor never spawned.
int tpuj_wait(long pid) {
  int code;
  if (lookup(pid, &code)) return code;
  for (;;) {
    int status;
    pid_t r = waitpid((pid_t)pid, &status, 0);
    if (r == (pid_t)pid) return record(pid, status);
    if (r < 0 && errno == EINTR) continue;
    if (r < 0 && errno == ECHILD) {
      // Another thread won the waitpid race; its record() is imminent —
      // unless that winner has already consumed the code and dropped the
      // slot (tpuj_forget): then there is nothing left to wait for, and
      // polling on cost a terminate that lost the race its whole 10 s.
      for (int i = 0; i < 2000 && tracked(pid); ++i) {
        if (lookup(pid, &code)) return code;
        sleep_ms(5);
      }
    }
    return -ECHILD;
  }
}

// Nonblocking poll: 1 = exited (*code_out set), 0 = still running,
// negative errno on error.
int tpuj_poll(long pid, int* code_out) {
  int code;
  if (lookup(pid, &code)) {
    *code_out = code;
    return 1;
  }
  int status;
  pid_t r = waitpid((pid_t)pid, &status, WNOHANG);
  if (r == 0) return 0;
  if (r == (pid_t)pid) {
    *code_out = record(pid, status);
    return 1;
  }
  if (errno == ECHILD && lookup(pid, &code)) {  // racing waiter recorded it
    *code_out = code;
    return 1;
  }
  return -(int)errno;
}

// Signal the child's process group (the whole subtree — a training harness
// that forked data-loader children must not leave orphans). No-op once the
// child is recorded dead.
int tpuj_signal(long pid, int sig) {
  int code;
  if (lookup(pid, &code)) return 0;
  if (kill((pid_t)-pid, sig) == 0) return 0;
  if (errno == ESRCH && kill((pid_t)pid, sig) == 0) return 0;
  return -(int)errno;
}

// Graceful stop: SIGTERM, poll up to grace_ms, escalate to SIGKILL.
// Returns the final normalized exit code (143 for a clean SIGTERM death,
// 137 after escalation), or negative errno.
int tpuj_terminate(long pid, int grace_ms) {
  int rc = tpuj_signal(pid, SIGTERM);
  if (rc < 0 && rc != -ESRCH) return rc;
  long waited = 0;
  int code;
  while (waited < grace_ms) {
    int r = tpuj_poll(pid, &code);
    if (r == 1) return code;
    if (r == -ECHILD) {
      // A concurrent tpuj_wait won the waitpid race and its record() has
      // not committed yet; tpuj_wait's registry-poll path resolves it.
      // Returning the raw -ECHILD here would be consumed as an "exit
      // code" and poison the caller's view of a recycled pid.
      return tpuj_wait(pid);
    }
    if (r < 0) return r;
    sleep_ms(10);
    waited += 10;
  }
  tpuj_signal(pid, SIGKILL);
  return tpuj_wait(pid);
}

// Kill whatever remains of the child's process GROUP, regardless of the
// leader's registry state. Used after the leader has been reaped: setsid
// group members (forked data loaders etc.) survive their leader, and the
// pod semantic is that they must not — a dead leader means a dead gang
// member, and its whole local process tree goes with it. ESRCH (group
// fully gone — the common case) is success.
int tpuj_kill_group(long pid, int sig) {
  if (kill((pid_t)-pid, sig) == 0) return 0;
  return errno == ESRCH ? 0 : -(int)errno;
}

// Drop a reaped pid's registry slot (call after the exit code has been
// consumed; pids recycle, so a stale done-entry could lie about a future
// child that happens to get the same pid).
void tpuj_forget(long pid) {
  std::lock_guard<std::mutex> l(g_mu);
  g_procs.erase(pid);
}

// Registry size (spawned and not yet forgotten) — leak oracle for tests.
int tpuj_tracked_count() {
  std::lock_guard<std::mutex> l(g_mu);
  return (int)g_procs.size();
}

}  // extern "C"
