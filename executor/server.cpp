// In-sandbox executor server (TPU-native rebuild of the reference's Rust
// executor; behavior parity with executor/server.rs:68-241 — file
// upload/download routes, POST /execute with timeout and changed-file
// detection — re-designed for TPU):
//
//   * Paths are explicitly confined to their base directory (the reference
//     joined attacker-controlled absolute paths, server.rs:83).
//   * User code runs under plain CPython, not xonsh (reclaims the ~80 ms
//     startup acknowledged in server.rs:204) — or, by default, inside a warm
//     persistent runner process that has already imported JAX and initialized
//     the TPU at sandbox boot, so Execute latency excludes libtpu init and
//     device enumeration (seconds on TPU — the pool amortizes it; SURVEY.md §7
//     hard part #2).
//   * Changed-file detection is a recursive mtime+size diff, not the
//     reference's top-level-only ctime scan (server.rs:117-137).
//   * Dependency auto-install uses an AST import scan (deps.py) instead of
//     `upm guess` (server.rs:174-195), gated by APP_AUTO_INSTALL_DEPS.
//
// Env knobs: APP_LISTEN_ADDR (0.0.0.0:8000; port 0 = ephemeral, printed),
// APP_WORKSPACE (/workspace), APP_RUNTIME_PACKAGES (/runtime-packages),
// APP_PYTHON (python3), APP_WARM_RUNNER (1), APP_WARM_EAGER (1; 0 = warm-up
// waits for POST /warmup), APP_RUNNER_READY_TIMEOUT (180), APP_AUTO_INSTALL_DEPS
// (0), APP_DEFAULT_TIMEOUT (60), APP_MAX_OUTPUT_BYTES (10485760),
// APP_WORKSPACE_MANIFEST (1; 0 = legacy wire format: no sha256 manifest,
// plain-string `files` arrays, no /workspace-manifest route),
// APP_STORAGE_OBJECTS_DIR (unset; the control plane's content-addressed
// storage directory where the backend that spawned this server knows it to be
// visible from here: turns on POST /copy-from-storage/workspace/<rel>).
//
// Resource governance (limits.hpp): APP_LIMIT_MEMORY_BYTES,
// APP_LIMIT_CPU_SECONDS, APP_LIMIT_NPROC, APP_LIMIT_NOFILE,
// APP_LIMIT_FSIZE_BYTES, APP_LIMIT_DISK_BYTES set the server's caps-and-
// defaults (0 = off); a request's `limits` object can only tighten them.
// APP_LIMIT_POLL_INTERVAL (0.1) is the watchdog sampling cadence. Breaches
// kill the runner group and classify as a typed `violation` in the execute
// response (oom / disk_quota / nproc / cpu_time / output_cap) instead of a
// generic crash. The workspace disk quota also guards streaming PUTs (413).

#include <dirent.h>
#include <fcntl.h>
#include <poll.h>
#include <signal.h>
#include <sys/prctl.h>
#include <sys/stat.h>
#include <sys/types.h>
#include <sys/wait.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <climits>
#include <condition_variable>
#include <cstdarg>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <map>
#include <mutex>
#include <string>
#include <thread>
#include <vector>

#include "cgroup.hpp"
#include "http.hpp"
#include "json.hpp"
#include "limits.hpp"
#include "sha256.hpp"

// Runner session id, mirrored for the SIGTERM handler (async-signal-safe
// cleanup): the runner lives in its own session, so killing the server's
// group misses it, and the runner's own pipe-EOF watchdog cannot run while
// its main thread blocks in GIL-holding native code (e.g. TPU init). The
// server is therefore the one reliable place to reap it on shutdown.
volatile sig_atomic_t g_runner_sid = 0;

extern "C" void handle_shutdown_signal(int) {
  pid_t sid = g_runner_sid;
  if (sid > 0) kill(-sid, SIGKILL);
  _exit(143);
}

namespace {

std::string env_or(const char* name, const std::string& dflt) {
  const char* v = getenv(name);
  return v && *v ? std::string(v) : dflt;
}

double env_num(const char* name, double dflt) {
  const char* v = getenv(name);
  return v && *v ? atof(v) : dflt;
}

bool env_flag(const char* name, bool dflt) {
  const char* v = getenv(name);
  if (!v || !*v) return dflt;
  return strcmp(v, "0") != 0 && strcasecmp(v, "false") != 0;
}

void log_msg(const char* fmt, ...) {
  va_list ap;
  va_start(ap, fmt);
  fprintf(stderr, "[executor] ");
  vfprintf(stderr, fmt, ap);
  fprintf(stderr, "\n");
  va_end(ap);
}

// ---------------------------------------------------------------------------
// Path confinement (SURVEY.md §0.4 fix).

// Normalizes a URL path to a safe relative path: strips leading slashes,
// resolves "." segments, rejects "..". Returns empty string on rejection.
std::string sanitize_rel_path(const std::string& raw) {
  std::vector<std::string> parts;
  std::string cur;
  for (size_t i = 0; i <= raw.size(); ++i) {
    char c = i < raw.size() ? raw[i] : '/';
    if (c == '/') {
      if (cur == ".." ) return "";
      if (!cur.empty() && cur != ".") parts.push_back(cur);
      cur.clear();
    } else {
      cur += c;
    }
  }
  if (parts.empty()) return "";
  std::string out;
  for (size_t i = 0; i < parts.size(); ++i) {
    if (i) out += '/';
    out += parts[i];
  }
  return out;
}

// Joins base+rel and verifies the realpath of the existing prefix stays under
// the realpath of base (guards against symlinks planted by user code).
bool confine(const std::string& base, const std::string& rel, std::string& out) {
  char base_real[PATH_MAX];
  if (!realpath(base.c_str(), base_real)) return false;
  std::string candidate = std::string(base_real) + "/" + rel;
  // Resolve the deepest existing ancestor of candidate.
  std::string probe = candidate;
  std::string suffix;
  while (true) {
    char resolved[PATH_MAX];
    if (realpath(probe.c_str(), resolved)) {
      std::string r(resolved);
      std::string full = suffix.empty() ? r : r + "/" + suffix;
      std::string base_s(base_real);
      if (full == base_s || full.compare(0, base_s.size() + 1, base_s + "/") == 0) {
        out = full;
        return true;
      }
      return false;
    }
    size_t slash = probe.rfind('/');
    if (slash == std::string::npos || probe == base_real) return false;
    std::string last = probe.substr(slash + 1);
    suffix = suffix.empty() ? last : last + "/" + suffix;
    probe = probe.substr(0, slash);
  }
}

// Race-free confined open: walks `rel` one component at a time from an open
// base-dir fd, with O_NOFOLLOW at every step, so user code cannot swap a
// symlink into place between a check and the use (TOCTOU). `create_dirs`
// makes intermediate directories. Returns an open fd for the final component
// (opened with `flags|O_NOFOLLOW`) or -1.
int open_confined(const std::string& base, const std::string& rel, int flags,
                  mode_t mode, bool create_dirs) {
  int cur = open(base.c_str(), O_DIRECTORY | O_RDONLY | O_CLOEXEC);
  if (cur < 0) return -1;
  size_t start = 0;
  while (true) {
    size_t slash = rel.find('/', start);
    bool last = slash == std::string::npos;
    std::string comp = rel.substr(start, last ? std::string::npos : slash - start);
    if (last) {
      int fd = openat(cur, comp.c_str(), flags | O_NOFOLLOW | O_CLOEXEC, mode);
      int saved = errno;
      close(cur);
      errno = saved;
      return fd;
    }
    if (create_dirs) {
      if (mkdirat(cur, comp.c_str(), 0777) != 0 && errno != EEXIST) {
        close(cur);
        return -1;
      }
    }
    int next = openat(cur, comp.c_str(), O_DIRECTORY | O_RDONLY | O_NOFOLLOW | O_CLOEXEC);
    int saved = errno;
    close(cur);
    errno = saved;
    if (next < 0) return -1;
    cur = next;
    start = slash + 1;
  }
}

// ---------------------------------------------------------------------------
// Workspace snapshot / diff (recursive; replaces server.rs:117-137).

struct FileSig {
  int64_t mtime_ns;
  int64_t size;
  bool operator==(const FileSig& o) const {
    return mtime_ns == o.mtime_ns && size == o.size;
  }
};

FileSig sig_of(const struct stat& st) {
  return FileSig{st.st_mtim.tv_sec * 1000000000LL + st.st_mtim.tv_nsec,
                 st.st_size};
}

void scan_dir(const std::string& base, const std::string& rel,
              std::map<std::string, FileSig>& out) {
  std::string dir = rel.empty() ? base : base + "/" + rel;
  DIR* d = opendir(dir.c_str());
  if (!d) return;
  while (dirent* e = readdir(d)) {
    std::string name = e->d_name;
    if (name == "." || name == "..") continue;
    std::string rel_child = rel.empty() ? name : rel + "/" + name;
    std::string full = base + "/" + rel_child;
    struct stat st;
    if (lstat(full.c_str(), &st) != 0) continue;
    if (S_ISDIR(st.st_mode)) {
      scan_dir(base, rel_child, out);
    } else if (S_ISREG(st.st_mode)) {
      out[rel_child] = sig_of(st);
    }
  }
  closedir(d);
}

std::vector<std::string> diff_snapshots(const std::map<std::string, FileSig>& before,
                                        const std::map<std::string, FileSig>& after) {
  std::vector<std::string> changed;
  for (const auto& [path, sig] : after) {
    auto it = before.find(path);
    if (it == before.end() || !(it->second == sig)) changed.push_back(path);
  }
  return changed;
}

// ---------------------------------------------------------------------------
// Workspace manifest: rel path -> content sha256, the executor half of the
// delta transfer protocol. Uploads hash as they stream in; the post-execute
// scan and GET /workspace-manifest rehash lazily — only entries whose
// size/mtime signature no longer matches. Protected by its own mutex
// (uploads are concurrent; /execute holds exec_mutex, which never nests
// inside this one).

struct ManifestEntry {
  std::string sha;
  FileSig sig;
};

std::map<std::string, ManifestEntry> g_ws_manifest;
std::mutex g_ws_manifest_mutex;

// Second manifest over the JAX compilation-cache dir: the executor half of
// the FLEET compile cache (control plane seeds hot entries at spawn via
// conditional PUTs and harvests new compiles at turnover via GET). Same
// entry/signature machinery as the workspace manifest, its own mutex (the
// two are never nested).
std::map<std::string, ManifestEntry> g_cc_manifest;
std::mutex g_cc_manifest_mutex;

// Hashes one workspace file through the same race-free confined open the
// transfer routes use (user code may have planted symlinks). Returns false
// when the file vanished or cannot be read; `sig_out` gets the fstat
// signature of the bytes actually hashed.
bool hash_workspace_file(const std::string& workspace, const std::string& rel,
                         std::string& hex_out, FileSig* sig_out) {
  int fd = open_confined(workspace, rel, O_RDONLY, 0, /*create_dirs=*/false);
  if (fd < 0) return false;
  struct stat st;
  if (fstat(fd, &st) != 0 || !S_ISREG(st.st_mode)) {
    close(fd);
    return false;
  }
  minisha::Sha256 hasher;
  char buf[1 << 16];
  ssize_t n;
  while ((n = read(fd, buf, sizeof(buf))) > 0) hasher.update(buf, static_cast<size_t>(n));
  close(fd);
  if (n < 0) return false;
  hex_out = hasher.hex();
  if (sig_out) *sig_out = sig_of(st);
  return true;
}

// Reconciles a manifest with its base dir as it exists NOW and returns
// rel -> sha: entries whose signature still matches keep their cached sha,
// changed/new files are rehashed, gone files are dropped. Caller must NOT
// hold `mutex`. Shared by the workspace manifest and the compile-cache
// manifest.
std::map<std::string, std::string> manifest_snapshot(
    const std::string& base, std::map<std::string, ManifestEntry>& manifest,
    std::mutex& mutex) {
  std::map<std::string, FileSig> on_disk;
  scan_dir(base, "", on_disk);
  std::map<std::string, std::string> out;
  std::lock_guard<std::mutex> lock(mutex);
  for (auto it = manifest.begin(); it != manifest.end();) {
    if (on_disk.find(it->first) == on_disk.end()) {
      it = manifest.erase(it);
    } else {
      ++it;
    }
  }
  for (const auto& [rel, sig] : on_disk) {
    auto it = manifest.find(rel);
    if (it != manifest.end() && it->second.sig == sig) {
      out[rel] = it->second.sha;
      continue;
    }
    std::string hex;
    FileSig fresh;
    if (!hash_workspace_file(base, rel, hex, &fresh)) continue;
    manifest[rel] = ManifestEntry{hex, fresh};
    out[rel] = hex;
  }
  return out;
}

// After a forgivable-looking rmdir failure (EBUSY/ENOTEMPTY with the
// recursive wipe reporting success), verifies by RE-SCANNING that nothing
// but empty mount points actually remains at/below the entry. The readdir
// snapshot the wipe worked from is stale by the time rmdir fails: user
// code that escaped the runner scrub (a reparented daemon) could have
// raced a file back in, and forgiving on the stale snapshot would let it
// cross the generation boundary through a "complete" /reset. Forgivable
// residue is exactly: a mount-point directory (st_dev differs from its
// parent's) that is EMPTY, or a directory containing only such residue.
bool only_mount_residue(int dfd, const char* name) {
  struct stat parent_st;
  if (fstat(dfd, &parent_st) != 0) return false;
  int fd = openat(dfd, name, O_DIRECTORY | O_RDONLY | O_NOFOLLOW | O_CLOEXEC);
  if (fd < 0) return false;
  struct stat st;
  if (fstat(fd, &st) != 0) {
    close(fd);
    return false;
  }
  bool is_mount = st.st_dev != parent_st.st_dev;
  DIR* d = fdopendir(fd);
  if (!d) {
    close(fd);
    return false;
  }
  bool ok = true;
  bool has_entries = false;
  while (dirent* e = readdir(d)) {
    std::string entry = e->d_name;
    if (entry == "." || entry == "..") continue;
    has_entries = true;
    if (is_mount || !only_mount_residue(dirfd(d), entry.c_str())) {
      ok = false;  // a non-empty mount point, or non-mount residue below
      break;
    }
  }
  if (!is_mount && !has_entries) {
    // An EMPTY NON-mount dir is plain removable residue, not a mount the
    // wipe is powerless against: the recursive wipe deletes empty dirs,
    // so one still standing here can only have been raced in after the
    // wipe's readdir snapshot (its NAME is attacker-chosen data). Without
    // this check the recursion forgave any empty dir — mount or not —
    // letting such names cross the generation boundary through a
    // "complete" /reset.
    ok = false;
  }
  closedir(d);
  return ok;
}

// Recursively deletes everything INSIDE dfd (the dir itself survives — it is
// the warm runner's cwd), except the subtree rooted at `preserve` (an
// absolute path; empty = preserve nothing). fd-relative with O_NOFOLLOW so
// user-planted symlinks are unlinked, never followed. `dir_path` is the
// lexical absolute path of dfd, used only for the preserve comparison.
// Returns true when every non-preserved entry was removed.
bool wipe_dirfd_children(int dfd, const std::string& dir_path,
                         const std::string& preserve) {
  DIR* d = fdopendir(dup(dfd));
  if (!d) return false;
  bool ok = true;
  while (dirent* e = readdir(d)) {
    std::string name = e->d_name;
    if (name == "." || name == "..") continue;
    std::string child_path = dir_path + "/" + name;
    if (!preserve.empty()) {
      if (child_path == preserve) {
        // The preserved subtree itself — but only if it still IS a real
        // directory. The comparison alone is lexical: user code that
        // empties the cache dir, rmdirs it, and plants a symlink (or file)
        // at the same path would get the planted node preserved through
        // /reset, redirecting the next generation's cache writes wherever
        // it points. Verify without following, unlink impostors, and
        // report the wipe incomplete so the sandbox is disposed.
        struct stat st;
        if (fstatat(dfd, name.c_str(), &st, AT_SYMLINK_NOFOLLOW) == 0 &&
            S_ISDIR(st.st_mode)) {
          continue;
        }
        if (unlinkat(dfd, name.c_str(), 0) != 0) {
          unlinkat(dfd, name.c_str(), AT_REMOVEDIR);
        }
        ok = false;
        continue;
      }
      if (preserve.rfind(child_path + "/", 0) == 0) {
        // The preserved dir lives somewhere below this child: recurse so
        // its siblings still wipe, but keep the ancestor chain intact.
        int child = openat(dfd, name.c_str(),
                           O_DIRECTORY | O_RDONLY | O_NOFOLLOW | O_CLOEXEC);
        if (child >= 0) {
          if (!wipe_dirfd_children(child, child_path, preserve)) ok = false;
          close(child);
        } else {
          // The ancestor is not an openable real dir — user code replaced
          // it (symlink/file). Reporting success would let the planted
          // node survive a "complete" wipe.
          ok = false;
        }
        continue;
      }
    }
    if (unlinkat(dfd, name.c_str(), 0) == 0) continue;
    int child = openat(dfd, name.c_str(),
                       O_DIRECTORY | O_RDONLY | O_NOFOLLOW | O_CLOEXEC);
    if (child < 0) {
      ok = false;  // neither unlinkable nor a walkable dir: left behind
      continue;
    }
    bool child_ok = wipe_dirfd_children(child, child_path, std::string());
    if (!child_ok) ok = false;
    close(child);
    if (unlinkat(dfd, name.c_str(), AT_REMOVEDIR) != 0) {
      // A fully-wiped dir can still be unremovable for two forgivable
      // reasons: it IS a mount point (EBUSY — e.g. a volume an operator
      // mounted under an extra wipe dir), or it CONTAINS one deeper down
      // (ENOTEMPTY — without this the forgiveness would stop at depth one
      // and every ancestor of a nested mount would fail the wipe). Either
      // way nothing may cross the generation boundary: child_ok is a
      // stale readdir snapshot, so only_mount_residue re-scans and
      // forgives only when empty mount points are truly all that remain.
      int err = errno;
      if (!(child_ok && (err == EBUSY || err == ENOTEMPTY) &&
            only_mount_residue(dfd, name.c_str()))) {
        ok = false;
      }
    }
  }
  closedir(d);
  return ok;
}

bool wipe_dir_children(const std::string& path,
                       const std::string& preserve = std::string()) {
  int fd = open(path.c_str(), O_DIRECTORY | O_RDONLY | O_NOFOLLOW | O_CLOEXEC);
  if (fd < 0) return false;
  bool ok = wipe_dirfd_children(fd, path, preserve);
  close(fd);
  return ok;
}

// ---------------------------------------------------------------------------
// Subprocess plumbing.

std::string read_file_capped(const std::string& path, size_t cap, bool* truncated) {
  std::string out;
  int fd = open(path.c_str(), O_RDONLY);
  if (fd < 0) return out;
  char buf[1 << 16];
  while (out.size() < cap) {
    ssize_t n = read(fd, buf, std::min(sizeof(buf), cap - out.size()));
    if (n <= 0) break;
    out.append(buf, static_cast<size_t>(n));
  }
  // detect truncation: one more byte available?
  char extra;
  if (read(fd, &extra, 1) == 1 && truncated) *truncated = true;
  close(fd);
  return out;
}

bool write_file(const std::string& path, const std::string& data) {
  int fd = open(path.c_str(), O_WRONLY | O_CREAT | O_TRUNC, 0644);
  if (fd < 0) return false;
  size_t off = 0;
  while (off < data.size()) {
    ssize_t n = write(fd, data.data() + off, data.size() - off);
    if (n < 0) {
      if (errno == EINTR) continue;
      close(fd);
      return false;
    }
    off += static_cast<size_t>(n);
  }
  close(fd);
  return true;
}

struct ExecOutcome {
  int exit_code = -1;
  bool timed_out = false;
};

// Runs argv with stdout/stderr redirected to files, cwd=workspace, its own
// process group; kills the whole group on timeout. `rlimits` (optional)
// boxes the child with the setrlimit set before exec; `watchdog` (optional)
// learns the child pid the moment it exists, so group-level RSS/CPU/nproc
// enforcement covers the whole run.
ExecOutcome run_subprocess(const std::vector<std::string>& argv,
                           const std::string& cwd, const std::string& stdout_path,
                           const std::string& stderr_path, double timeout_s,
                           const minijson::Value* extra_env,
                           const limits::LimitSpec* rlimits = nullptr,
                           limits::Watchdog* watchdog = nullptr,
                           const std::string* cgroup_procs = nullptr) {
  ExecOutcome out;
  pid_t parent = getpid();
  pid_t pid = fork();
  if (pid < 0) return out;
  if (pid == 0) {
    setsid();
    // setsid() detaches us from the server's process group, so an external
    // SIGKILL of the server's group would orphan user code — die with the
    // server instead (checking for the fork↔prctl race). Thread-exit
    // semantics of PDEATHSIG are safe here: the forking handler thread
    // blocks in the waitpid loop below until this child is gone.
    prctl(PR_SET_PDEATHSIG, SIGKILL);
    if (getppid() != parent) _exit(127);
    // Self-attach to the per-run cgroup scope BEFORE exec (race-free:
    // every byte user code ever allocates is inside the box). Failure is
    // non-fatal — rlimits+watchdog still govern.
    if (cgroup_procs && !cgroup_procs->empty())
      cgroup::write_file(*cgroup_procs, "0");
    if (rlimits) limits::apply_child_rlimits(*rlimits);
    if (!cwd.empty()) {
      if (chdir(cwd.c_str()) != 0) _exit(127);
    }
    int so = open(stdout_path.c_str(), O_WRONLY | O_CREAT | O_TRUNC, 0644);
    int se = open(stderr_path.c_str(), O_WRONLY | O_CREAT | O_TRUNC, 0644);
    if (so >= 0) dup2(so, 1);
    if (se >= 0) dup2(se, 2);
    if (extra_env && extra_env->is_object()) {
      for (const auto& [k, v] : extra_env->as_object()) {
        // stringify non-strings for parity with the warm runner (str(v))
        std::string sv = v.is_string() ? v.as_string() : v.dump();
        setenv(k.c_str(), sv.c_str(), 1);
      }
    }
    std::vector<char*> cargv;
    for (const auto& a : argv) cargv.push_back(const_cast<char*>(a.c_str()));
    cargv.push_back(nullptr);
    execvp(cargv[0], cargv.data());
    _exit(127);
  }
  if (watchdog) watchdog->set_leader(pid);
  // Parent: poll for exit until deadline.
  const int tick_ms = 20;
  double waited = 0;
  int status = 0;
  while (true) {
    pid_t r = waitpid(pid, &status, WNOHANG);
    if (r == pid) {
      if (WIFEXITED(status)) out.exit_code = WEXITSTATUS(status);
      else if (WIFSIGNALED(status)) out.exit_code = 128 + WTERMSIG(status);
      return out;
    }
    if (timeout_s > 0 && waited >= timeout_s) {
      kill(-pid, SIGKILL);
      waitpid(pid, &status, 0);
      out.timed_out = true;
      out.exit_code = -1;
      return out;
    }
    usleep(tick_ms * 1000);
    waited += tick_ms / 1000.0;
  }
}

// ---------------------------------------------------------------------------
// Device-health telemetry (GET /device-stats). Rounds 3 to 5 on the TPU rig
// showed the worst failure mode is a wedged device op (50-76 minutes of
// manual recovery by host reboot each time): the
// attach blocks for tens of minutes with /healthz still answering "ok",
// because nothing distinguished "busy" from "wedged". These globals are the
// raw signals a probe daemon needs to make that call: when the current
// attach (warm-up) started, when the current device op started and what its
// budget is, when the runner last produced evidence of life, and when a
// device op last SUCCEEDED. All atomics on purpose — the /device-stats
// handler must answer while exec_mutex/runner_mutex are held by exactly the
// wedged operation it exists to expose.

long long now_ms() {
  struct timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return ts.tv_sec * 1000LL + ts.tv_nsec / 1000000LL;
}

// CLOCK_MONOTONIC as seconds: the one clock of every stage timing in the
// `trace` blocks. The warm runner's time.monotonic() reads the same kernel
// clock on the same host, so the server's reading at the pipe write is an
// origin both processes share.
double mono_s() {
  struct timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return ts.tv_sec + ts.tv_nsec / 1e9;
}

std::atomic<long long> g_boot_ms{0};
// Warm-up (jax import + device attach) window: nonzero while one is running.
std::atomic<long long> g_attach_start_ms{0};
// Latency of the last SUCCESSFUL warm-up (the per-sandbox attach cost);
// -1 until one completes.
std::atomic<long long> g_attach_last_ms{-1};
// Current warm-runner device op (execute/reset round-trip): start + budget.
std::atomic<long long> g_op_start_ms{0};
std::atomic<long long> g_op_timeout_ms{0};
// Completion time of the last device op the runner answered successfully.
std::atomic<long long> g_last_op_ok_ms{0};
// Last time the runner wrote ANY bytes on its response pipe — the passive
// heartbeat. A runner pinned inside a wedged native call writes nothing, so
// this age grows exactly when the probe needs it to.
std::atomic<long long> g_runner_line_ms{0};
// Runner identity mirrors, updated only at start/kill: the stats handler
// must not touch WarmRunner fields (they are runner_mutex-protected, and
// that mutex is held for the whole duration of the op being diagnosed).
std::atomic<long long> g_runner_pid_stat{0};
std::atomic<bool> g_runner_ready_stat{false};
std::atomic<int> g_device_count_stat{0};
std::mutex g_device_info_mutex;  // guards the three values below only
std::string g_device_backend_stat = "none";
std::string g_device_kind_stat;
// Where the last attach's seconds went, as the runner's ready line said it
// (`attach_stages`: interpreter_start, import_jax, distributed_init, devices,
// first_compile).
minijson::Value g_attach_stages_stat;

// cgroup-v2 hard enforcement (cgroup.hpp): the boot-time delegation verdict,
// the long-lived scope boxing the warm runner group (bounded by the
// APP_LIMIT_* caps for the sandbox's whole life — per-request tighten-only
// overrides stay the watchdog's job), and the procs path a freshly forked
// runner self-attaches to. The verdict and its fallback reason ride
// /healthz so the control plane (and the test suite's auto-skip) can see
// which enforcement mode this sandbox actually runs in. Scope event reads
// happen only under exec_mutex (the execute/batch paths); the procs string
// is written once at boot, before any fork reads it.
cgroup::Runtime g_cgroup;
cgroup::Scope g_runner_scope;
std::string g_runner_cgroup_procs;
std::atomic<long long> g_run_scope_seq{0};

// Per-chip lease fencing: the generation token the control plane minted
// for THIS sandbox's claim on its chips, recorded at attach (POST /lease).
// Every dispatch carries its token in `x-lease-token`; a mismatch is a
// claim minted for a fenced predecessor on the same chips — rejected with
// a typed 409 BEFORE any lock is taken, so a stale dispatch cannot even
// queue behind the device plane it must never touch (how rounds 3 to 5
// re-wedged a chip for its next holder). Tiny mutex, never held across I/O.
std::mutex g_lease_mutex;
std::string g_lease_token;

// Resident set size of `pid` in bytes via /proc/<pid>/statm; -1 on failure.
long long rss_bytes_of(long long pid) {
  if (pid <= 0) return -1;
  char path[64];
  snprintf(path, sizeof(path), "/proc/%lld/statm", pid);
  FILE* f = fopen(path, "r");
  if (!f) return -1;
  long long pages_total = 0, pages_resident = 0;
  int n = fscanf(f, "%lld %lld", &pages_total, &pages_resident);
  fclose(f);
  if (n != 2) return -1;
  return pages_resident * static_cast<long long>(sysconf(_SC_PAGESIZE));
}

// ---------------------------------------------------------------------------
// Warm runner: a persistent Python process that pre-imports JAX (initializing
// the TPU) at sandbox boot and then executes scripts on demand. Protocol:
// newline-delimited JSON over the runner's fd 3 (requests) and fd 4
// (responses); user stdout/stderr go to files named in each request.

class WarmRunner {
 public:
  WarmRunner(std::string python, std::string runner_script, std::string workspace,
             double ready_timeout_s)
      : python_(std::move(python)),
        runner_script_(std::move(runner_script)),
        workspace_(std::move(workspace)),
        ready_timeout_s_(ready_timeout_s),
        interrupt_grace_s_(env_num("APP_RUNNER_INTERRUPT_GRACE_S", 20.0)) {}

  bool start() {
    int req_pipe[2];   // server writes → runner fd 3
    int resp_pipe[2];  // runner fd 4 → server reads
    if (pipe(req_pipe) != 0 || pipe(resp_pipe) != 0) return false;
    pid_t parent = getpid();
    pid_ = fork();
    if (pid_ < 0) return false;
    if (pid_ == 0) {
      setsid();
      // No PR_SET_PDEATHSIG here: it fires when the FORKING THREAD exits,
      // and runner restarts happen on short-lived per-request handler
      // threads — the fresh runner would be killed as soon as that request
      // finished. Server-death cleanup is handled by the runner itself: its
      // request-pipe read returns EOF when the server dies and it _exits
      // immediately (runner.py main loop).
      if (getppid() != parent) _exit(127);
      // Join the runner's cgroup scope BEFORE exec: from the first
      // instruction of runner.py, the kernel enforces memory.max/pids.max
      // over the whole runner group ("0" = the writing process). Failure
      // is non-fatal — the rlimits+watchdog layers still govern.
      if (!g_runner_cgroup_procs.empty())
        cgroup::write_file(g_runner_cgroup_procs, "0");
      if (chdir(workspace_.c_str()) != 0) _exit(127);
      // Shuffle pipe ends to fds 3/4 via safe high fds (the pipe fds may
      // themselves be 3/4, so a direct dup2 could clobber an end).
      int r = fcntl(req_pipe[0], F_DUPFD, 10);
      int w = fcntl(resp_pipe[1], F_DUPFD, 10);
      close(req_pipe[0]);
      close(req_pipe[1]);
      close(resp_pipe[0]);
      close(resp_pipe[1]);
      dup2(r, 3);
      dup2(w, 4);
      close(r);
      close(w);
      execlp(python_.c_str(), python_.c_str(), "-u", runner_script_.c_str(),
             (char*)nullptr);
      _exit(127);
    }
    close(req_pipe[0]);
    close(resp_pipe[1]);
    req_fd_ = req_pipe[1];
    resp_fd_ = resp_pipe[0];
    g_runner_sid = pid_;
    // Wait for the ready line (runner imports jax → can take seconds on TPU;
    // that's the point: it happens at sandbox warm-up, not at Execute time).
    std::string line;
    if (!read_line(line, ready_timeout_s_)) {
      log_msg("warm runner failed to become ready");
      stop();
      return false;
    }
    std::string device_kind;
    minijson::Value attach_stages;
    try {
      auto msg = minijson::parse(line);
      ready_ = msg.get_bool("ready", false);
      backend_ = msg.get_string("backend", "unknown");
      device_count_ = static_cast<int>(msg.get_number("device_count", 0));
      device_kind = msg.get_string("device_kind", "");
      attach_stages = msg.get("attach_stages");
    } catch (...) {
      ready_ = false;
    }
    g_runner_pid_stat = pid_;
    g_runner_ready_stat = ready_;
    g_device_count_stat = device_count_;
    {
      std::lock_guard<std::mutex> dlock(g_device_info_mutex);
      g_device_backend_stat = backend_;
      g_device_kind_stat = device_kind;
      g_attach_stages_stat = attach_stages;
    }
    log_msg("warm runner ready=%d backend=%s devices=%d", (int)ready_,
            backend_.c_str(), device_count_);
    if (attach_stages.is_object())
      log_msg("warm runner attach stages (s): %s", attach_stages.dump().c_str());
    // ready=false is the runner saying its jax warm-up failed or attached
    // another platform than it was started for: reap it (it may hold the
    // chip) and let the warm-state machine report the failure.
    if (!ready_) stop();
    return ready_;
  }

  bool alive() const { return pid_ > 0 && ready_; }
  pid_t pid() const { return pid_; }
  const std::string& backend() const { return backend_; }
  int device_count() const { return device_count_; }

  enum class ExecResult { kOk, kTimeout, kDied, kInterrupted };

  // Generation reset: scrub the previous sandbox's traces from the warm
  // process (stray children, workspace modules, env/cwd) while keeping the
  // device lease. False ⇒ the runner is unscrubbable (killed) and the whole
  // process must be disposed.
  // `sent_mono` is the caller's mono_s() at the call; the runner's reply
  // (its `stages` among it) is handed back for the `/reset` trace block.
  bool reset(double timeout_s, double sent_mono, minijson::Value& resp) {
    minijson::Object reqo;
    reqo["op"] = minijson::Value(std::string("reset"));
    reqo["sent_mono"] = minijson::Value(sent_mono);
    if (execute(minijson::Value(reqo).dump(), timeout_s, resp) !=
        ExecResult::kOk)
      return false;
    if (!resp.get_bool("ok", false)) {
      kill_runner();
      return false;
    }
    return true;
  }

  // kTimeout = deadline expired (runner killed); kDied = runner crashed or
  // spoke garbage (killed); kInterrupted = deadline expired but cooperative
  // cancellation worked — the runner unwound user code via SIGINT, reported,
  // and is still alive with its device lease AND in-process state intact —
  // the caller keeps serving warm and must NOT scrub (to a session the
  // interrupt is just a failed request; pool turnover resets between
  // tenants via /reset as usual). It matters doubly on an accelerator:
  // a SIGKILLed runner costs the sandbox a full re-attach.
  // `allow_interrupt` gates the SIGINT grace to USER-code executes:
  // control ops (reset) must keep crisp kill-on-timeout semantics — their
  // handlers don't expect KeyboardInterrupt, and a late "interrupted"
  // verdict would misread a successful-but-slow reset as failure.
  ExecResult execute(const std::string& request_json, double timeout_s,
                     minijson::Value& response, bool allow_interrupt = false) {
    // Every runner round-trip is a device op from the probe's perspective
    // (execute, batch, reset): open the telemetry window so /device-stats
    // can report how long the CURRENT op has been running against what
    // budget, and stamp the success time when the runner actually answers.
    g_op_timeout_ms = timeout_s > 0
                          ? static_cast<long long>(timeout_s * 1000.0)
                          : 0;
    g_op_start_ms = now_ms();
    ExecResult result = execute_inner(request_json, timeout_s, response,
                                      allow_interrupt);
    if (result == ExecResult::kOk || result == ExecResult::kInterrupted)
      g_last_op_ok_ms = now_ms();
    g_op_start_ms = 0;
    return result;
  }

  ExecResult execute_inner(const std::string& request_json, double timeout_s,
                           minijson::Value& response, bool allow_interrupt) {
    std::string line = request_json + "\n";
    size_t off = 0;
    while (off < line.size()) {
      ssize_t n = write(req_fd_, line.data() + off, line.size() - off);
      if (n < 0) {
        if (errno == EINTR) continue;
        kill_runner();
        return ExecResult::kDied;
      }
      off += static_cast<size_t>(n);
    }
    std::string resp_line;
    bool timed_out = false;
    if (!read_line(resp_line, timeout_s, &timed_out)) {
      if (allow_interrupt && timed_out && interrupt_grace_s_ > 0 && pid_ > 0) {
        // Cooperative cancellation first: SIGINT surfaces in the user code
        // as KeyboardInterrupt, the runner's report-don't-die handler
        // writes a response, and the process (with its device lease)
        // survives. Python only delivers the signal between bytecodes, so
        // a runner pinned inside a long native call (an XLA compile) may
        // outlast the grace — then we fall through to the kill, as before.
        kill(-pid_, SIGINT);
        bool late_timeout = false;
        std::string late_line;
        if (read_line(late_line, interrupt_grace_s_, &late_timeout)) {
          log_msg("execute timeout: runner unwound via SIGINT (kept alive)");
          return ExecResult::kInterrupted;
        }
        log_msg("execute timeout: SIGINT grace (%.0fs) expired; killing",
                interrupt_grace_s_);
      }
      kill_runner();
      return timed_out ? ExecResult::kTimeout : ExecResult::kDied;
    }
    try {
      response = minijson::parse(resp_line);
      return ExecResult::kOk;
    } catch (...) {
      kill_runner();
      return ExecResult::kDied;
    }
  }

  void kill_runner() {
    g_runner_sid = 0;
    g_runner_pid_stat = 0;
    g_runner_ready_stat = false;
    if (pid_ > 0) {
      kill(-pid_, SIGKILL);
      waitpid(pid_, nullptr, 0);
    }
    pid_ = -1;
    ready_ = false;
    if (req_fd_ >= 0) close(req_fd_);
    if (resp_fd_ >= 0) close(resp_fd_);
    req_fd_ = resp_fd_ = -1;
    resp_buf_.clear();  // stale bytes from a dead runner must not leak forward
  }

  void stop() { kill_runner(); }

 private:
  bool read_line(std::string& line, double timeout_s, bool* timed_out = nullptr) {
    // Event-driven: poll() blocks for the full remaining budget — no
    // fixed-interval ticks on the Execute path (VERDICT r2 #6).
    struct timespec start;
    clock_gettime(CLOCK_MONOTONIC, &start);
    while (true) {
      size_t nl = resp_buf_.find('\n');
      if (nl != std::string::npos) {
        line = resp_buf_.substr(0, nl);
        resp_buf_.erase(0, nl + 1);
        return true;
      }
      int wait_ms = -1;  // no timeout: block until data or EOF
      if (timeout_s > 0) {
        struct timespec now;
        clock_gettime(CLOCK_MONOTONIC, &now);
        double elapsed = (now.tv_sec - start.tv_sec) +
                         (now.tv_nsec - start.tv_nsec) / 1e9;
        double remaining = timeout_s - elapsed;
        if (remaining <= 0) {
          if (timed_out) *timed_out = true;
          return false;
        }
        wait_ms = static_cast<int>(remaining * 1000) + 1;
      }
      struct pollfd pfd{resp_fd_, POLLIN, 0};
      int r = poll(&pfd, 1, wait_ms);
      if (r < 0 && errno != EINTR) return false;
      if (r > 0) {
        char buf[1 << 14];
        ssize_t n = read(resp_fd_, buf, sizeof(buf));
        if (n <= 0) return false;
        // Passive heartbeat: any bytes from the runner are proof of life
        // (a wedged native call writes nothing, so this age grows).
        g_runner_line_ms = now_ms();
        resp_buf_.append(buf, static_cast<size_t>(n));
      }
    }
  }

  std::string python_, runner_script_, workspace_;
  double ready_timeout_s_ = 180.0;
  double interrupt_grace_s_ = 20.0;
  pid_t pid_ = -1;
  int req_fd_ = -1, resp_fd_ = -1;
  bool ready_ = false;
  std::string backend_ = "none";
  int device_count_ = 0;
  std::string resp_buf_;
};

// ---------------------------------------------------------------------------

struct ServerState {
  std::string workspace;
  std::string runtime_packages;
  std::string python;
  std::string runner_script;
  std::string deps_script;
  std::string launch_script;
  bool warm_enabled = true;
  bool warm_eager = true;  // start warm-up at boot (pods); 0 = wait for /warmup
  // The warm runner imports jax and attaches the device (APP_WARM_IMPORT_JAX,
  // the same variable runner.py reads). A chip belongs to one process, so
  // beside such a runner no user code ever runs in a cold subprocess: it
  // would fail to attach, or run on the host CPU unnoticed.
  bool runner_holds_device = true;
  bool auto_install = false;
  // Workspace-manifest protocol (delta transfers). 0 = legacy wire behavior:
  // no sha256 hashing, plain-string `files` arrays, 404 on
  // /workspace-manifest, If-None-Match ignored — exactly the pre-manifest
  // binary, which is also how the control plane's fallback path is tested.
  bool manifest_enabled = true;
  // The control plane's storage directory (objects named by the sha256 of
  // their bytes), stated by the backend that spawned this server when both
  // live on one host (APP_STORAGE_OBJECTS_DIR). Empty = not visible from
  // here: no copy route, every input file arrives through the PUT.
  std::string storage_dir;
  // Fleet compile cache (JAX persistent compilation cache served over
  // HTTP): the dir JAX_COMPILATION_CACHE_DIR names, exposed as
  // GET /compile-cache-manifest + hash-negotiated PUT/GET under
  // /compile-cache/. APP_COMPILE_CACHE=0 (or no cache dir) removes the
  // routes entirely — what an old binary answers too. The dir's subtree is
  // EXCLUDED from every /reset wipe: compiled kernels are exactly the
  // cross-generation state the wipe must not destroy (the historic /tmp
  // default made pod reuse silently discard them each turnover).
  std::string compile_cache_dir;
  bool compile_cache_enabled = false;
  // Extra directories whose CONTENTS are wiped on /reset (colon-separated;
  // "~/x" = HOME-relative; missing dirs are fine). Closes the cross-
  // generation channels outside workspace/runtime-packages: the sandbox's
  // private /tmp (pods; locally the backend points TMPDIR at a per-sandbox
  // dir instead — the host /tmp is shared and must not be wiped) and
  // ~/.local (pip --user installs land on sys.path).
  std::vector<std::string> extra_wipe_dirs;
  int num_hosts = 1;  // >1 → this sandbox is one host of a multi-host slice
  double default_timeout = 60.0;
  size_t max_output = 10 * 1024 * 1024;
  // Resource-governance caps-and-defaults (APP_LIMIT_*; see limits.hpp) and
  // the watchdog's sampling cadence.
  limits::LimitSpec limit_caps;
  double limit_poll_interval = 0.1;
  // Strict lease-token mode (APP_LEASE_REQUIRE_TOKEN=1): once a lease is
  // recorded, a dispatch WITHOUT an x-lease-token is refused with a typed
  // 409 — for fleets whose control planes all stamp tokens (PR 13), where
  // a tokenless dispatch can only be a stale/foreign claim. Default off:
  // tokenless compatibility for old control planes and manual curl.
  bool lease_require_token = false;
  WarmRunner* runner = nullptr;
  std::mutex exec_mutex;
  std::mutex runner_mutex;
};

ServerState g_state;

// Warm-up state machine. The server announces its port and serves HTTP from
// the moment it boots; the warm runner's jax import / TPU init (seconds to
// minutes) runs on a background thread. Round 1 serialized these — readiness
// waited on TPU init, so any init slower than the control plane's ready
// timeout failed every spawn (the r01 bench killer). Now "reachable" and
// "TPU-hot" are separate facts: /healthz reports warm_state, /readyz gates
// k8s readiness on it, POST /warmup lets the control plane decide WHEN init
// runs (it holds the per-chip lease — see backends/local.py).
enum WarmState { kWarmOff = 0, kWarmPending = 1, kWarmReady = 2, kWarmFailed = 3 };
std::atomic<int> g_warm_state{kWarmOff};
std::atomic<bool> g_ever_ready{false};
std::mutex g_warm_transition_mutex;
// Signaled on every warm-state transition so execute-path waiters block on a
// condvar instead of spinning (VERDICT r2 #6).
std::condition_variable g_warm_cv;

const char* warm_state_name(int s) {
  switch (s) {
    case kWarmPending: return "pending";
    case kWarmReady: return "ready";
    case kWarmFailed: return "failed";
    default: return "off";
  }
}

// Kick off (or retry) warm-up on a background thread. Idempotent: no-op when
// already pending/ready. Failed → pending retries (used for the
// off-critical-path runner restart after a timeout kill).
void start_warm_async() {
  if (!g_state.warm_enabled || !g_state.runner) return;
  {
    std::lock_guard<std::mutex> l(g_warm_transition_mutex);
    int s = g_warm_state.load();
    if (s == kWarmPending || s == kWarmReady) return;
    if (s == kWarmFailed && g_state.num_hosts > 1) return;  // see below
    g_warm_state = kWarmPending;
    g_attach_start_ms = now_ms();  // the attach window /device-stats reports
  }
  std::thread([] {
    bool ok;
    {
      std::lock_guard<std::mutex> l(g_state.runner_mutex);
      ok = g_state.runner->start();
    }
    if (ok) g_ever_ready = true;
    long long attach_start = g_attach_start_ms.load();
    if (ok && attach_start > 0) g_attach_last_ms = now_ms() - attach_start;
    g_attach_start_ms = 0;
    {
      std::lock_guard<std::mutex> l(g_warm_transition_mutex);
      g_warm_state = ok ? kWarmReady : kWarmFailed;
    }
    g_warm_cv.notify_all();
    if (!ok) {
      // On a multi-host slice the runner IS the jax.distributed membership;
      // a lone restart could never rendezvous (its peers' runners are still
      // in the old cluster), so failure is terminal and the control plane
      // must dispose the whole slice group.
      log_msg("warm-up failed%s", g_state.num_hosts > 1
                                      ? " on a multi-host slice (terminal)"
                                      : "");
    }
  }).detach();
}

const std::string* prefix_base(const std::string& prefix) {
  if (prefix == "workspace") return &g_state.workspace;
  if (prefix == "runtime-packages") return &g_state.runtime_packages;
  if (prefix == "compile-cache" && g_state.compile_cache_enabled)
    return &g_state.compile_cache_dir;
  return nullptr;
}

// The manifest (map + mutex) negotiating transfers for a prefix, or
// nullptrs for unmanifested prefixes (runtime-packages; everything when
// the protocol is off).
void prefix_manifest(const std::string& prefix,
                     std::map<std::string, ManifestEntry>*& map_out,
                     std::mutex*& mutex_out) {
  map_out = nullptr;
  mutex_out = nullptr;
  if (prefix == "workspace" && g_state.manifest_enabled) {
    map_out = &g_ws_manifest;
    mutex_out = &g_ws_manifest_mutex;
  } else if (prefix == "compile-cache" && g_state.compile_cache_enabled) {
    map_out = &g_cc_manifest;
    mutex_out = &g_cc_manifest_mutex;
  }
}

// Splits "/workspace/a/b" → ("workspace", "a/b"). Tolerates the reference
// control plane's double-prefix URLs ("/workspace//workspace/x" — SURVEY.md
// §0.4) by stripping a repeated leading prefix segment.
bool split_target(const std::string& target, std::string& prefix, std::string& rel) {
  std::string t = target;
  while (!t.empty() && t[0] == '/') t.erase(0, 1);
  size_t slash = t.find('/');
  if (slash == std::string::npos) return false;
  prefix = t.substr(0, slash);
  rel = sanitize_rel_path(t.substr(slash + 1));
  if (rel.empty()) return false;
  // strip duplicated prefix ("workspace/workspace/x" from legacy clients)
  std::string dup = prefix + "/";
  if (rel.compare(0, dup.size(), dup) == 0) rel = rel.substr(dup.size());
  return !rel.empty();
}

// Where a transfer into a served directory lands, and the manifest that
// keeps its books: what the streamed PUT and the copy from storage share.
struct UploadTarget {
  std::string prefix;
  std::string rel;
  const std::string* base = nullptr;
  // nullptrs for unmanifested prefixes (see prefix_manifest)
  std::map<std::string, ManifestEntry>* mani = nullptr;
  std::mutex* mani_mutex = nullptr;
};

// Splits and resolves a transfer's target. Answers the refusal itself (400
// bad path, 404 unknown prefix; the body drained) and returns false.
bool resolve_upload_target(const std::string& target, minihttp::Conn& conn,
                           UploadTarget& out) {
  if (!split_target(target, out.prefix, out.rel)) {
    conn.drain_body();
    conn.send_response(400, "application/json", "{\"error\":\"bad path\"}");
    return false;
  }
  out.base = prefix_base(out.prefix);
  if (!out.base) {
    conn.drain_body();
    conn.send_response(404, "application/json", "{\"error\":\"unknown prefix\"}");
    return false;
  }
  prefix_manifest(out.prefix, out.mani, out.mani_mutex);
  return true;
}

// The conditional skip: the manifest says the file at `rel` already holds
// exactly the content `sha` names, and the disk signature still matches
// (user code may have touched it since).
bool target_already_holds(const UploadTarget& t, const std::string& sha) {
  if (!t.mani || sha.empty()) return false;
  FileSig cached{0, 0};
  {
    std::lock_guard<std::mutex> lock(*t.mani_mutex);
    auto it = t.mani->find(t.rel);
    if (it == t.mani->end() || it->second.sha != sha) return false;
    cached = it->second.sig;
  }
  struct stat st;
  int fd = open_confined(*t.base, t.rel, O_RDONLY, 0, /*create_dirs=*/false);
  bool fresh = fd >= 0 && fstat(fd, &st) == 0 && S_ISREG(st.st_mode) &&
               sig_of(st) == cached;
  if (fd >= 0) close(fd);
  return fresh;
}

// Opens the target for writing (a fresh or truncated regular file, confined
// to its base). Answers the refusal itself (the body drained) and returns -1.
int open_upload_target(const UploadTarget& t, minihttp::Conn& conn) {
  int fd = open_confined(*t.base, t.rel, O_WRONLY | O_CREAT | O_TRUNC, 0644,
                         /*create_dirs=*/true);
  if (fd < 0) {
    int status = errno == ELOOP || errno == ENOTDIR ? 403 : 500;
    conn.drain_body();
    conn.send_response(status, "application/json",
                       "{\"error\":\"open failed (confined)\"}");
  }
  return fd;
}

// Workspace disk quota guards the transfer paths too: without it a client
// (or a compromised control plane) could fill the sandbox disk through
// uploads that never run any code. Returns the bytes this upload may still
// write (LLONG_MAX where no quota applies): usage is measured once at upload
// start, after O_TRUNC zeroed any file being overwritten. With the manifest
// on, usage comes from the cached entry sizes (O(entries), no IO) — a full
// recursive walk per upload would make an N-file sync O(N^2) stats; without
// it, the walk.
long long upload_quota_room(const UploadTarget& t) {
  long long disk_cap =
      t.prefix == "workspace" ? g_state.limit_caps.disk_bytes : 0;
  if (disk_cap <= 0) return LLONG_MAX;
  long long usage_before = 0;
  if (t.mani) {
    // Exclude the entry for the path being overwritten: O_TRUNC already
    // freed those bytes, so counting the stale size would 413 legitimate
    // re-uploads of changed files (the delta-sync's normal path) on any
    // workspace near half its quota.
    std::lock_guard<std::mutex> lock(*t.mani_mutex);
    for (const auto& [entry_rel, entry] : *t.mani)
      if (entry_rel != t.rel) usage_before += entry.sig.size;
  } else {
    usage_before = limits::dir_usage_bytes(*t.base);
  }
  return disk_cap - usage_before;
}

// Over quota: give the quota back (truncate what was written), drop the
// stale manifest entry, and answer with the typed violation. Closes fd.
void refuse_over_quota(const UploadTarget& t, int fd, minihttp::Conn& conn) {
  ftruncate(fd, 0);
  close(fd);
  if (t.mani) {
    std::lock_guard<std::mutex> lock(*t.mani_mutex);
    t.mani->erase(t.rel);
  }
  conn.drain_body();
  conn.send_response(413, "application/json",
                     "{\"error\":\"workspace disk quota exceeded\","
                     "\"violation\":\"disk_quota\"}");
}

// The upload landed: the manifest learns the sha now (where there is one),
// so the post-execute scan never rehashes these bytes. Closes fd.
void finish_upload(const UploadTarget& t, int fd, long long total,
                   const std::string& sha, minihttp::Conn& conn) {
  struct stat st;
  bool have_sig = fstat(fd, &st) == 0;
  close(fd);
  minijson::Object resp;
  resp["path"] = minijson::Value("/" + t.prefix + "/" + t.rel);
  resp["size"] = minijson::Value(static_cast<int64_t>(total));
  if (t.mani) {
    if (have_sig) {
      std::lock_guard<std::mutex> lock(*t.mani_mutex);
      (*t.mani)[t.rel] = ManifestEntry{sha, sig_of(st)};
    }
    resp["sha256"] = minijson::Value(sha);
  }
  conn.send_response(200, "application/json", minijson::Value(resp).dump());
}

bool write_all(int fd, const char* data, size_t size) {
  size_t off = 0;
  while (off < size) {
    ssize_t n = write(fd, data + off, size - off);
    if (n < 0) {
      if (errno == EINTR) continue;
      return false;
    }
    off += static_cast<size_t>(n);
  }
  return true;
}

void handle_upload(const minihttp::Request& req, minihttp::Conn& conn) {
  UploadTarget t;
  if (!resolve_upload_target(req.target, conn, t)) return;
  // Conditional upload: `If-None-Match: <sha256 of the body being sent>`.
  // On a hit the body is drained and skipped with a 304: no disk write, no
  // rehash. On mismatch the PUT proceeds as a normal upload — the header is
  // a claim about the body, so writing it is always correct.
  std::string cond = req.header("if-none-match");
  if (!cond.empty() && cond.front() == '"' && cond.back() == '"' && cond.size() >= 2)
    cond = cond.substr(1, cond.size() - 2);
  if (target_already_holds(t, cond)) {
    conn.drain_body();
    conn.send_response(304, "application/json", "");
    return;
  }
  int fd = open_upload_target(t, conn);
  if (fd < 0) return;
  long long room = upload_quota_room(t);
  // Stream-hash while writing.
  minisha::Sha256 hasher;
  long long total = 0;
  try {
    std::string chunk;
    while (true) {
      chunk.clear();
      if (conn.read_body_some(chunk, 1 << 20) == 0) break;
      if (total + static_cast<long long>(chunk.size()) > room) {
        refuse_over_quota(t, fd, conn);
        return;
      }
      if (t.mani) hasher.update(chunk.data(), chunk.size());
      if (!write_all(fd, chunk.data(), chunk.size())) {
        close(fd);
        conn.send_response(500, "application/json",
                           "{\"error\":\"write failed\"}");
        return;
      }
      total += static_cast<long long>(chunk.size());
    }
  } catch (...) {
    // Client aborted mid-body (the control plane cancels sibling uploads
    // when one fails): the connection is already doomed, but a long-lived
    // warm sandbox must not leak one fd per aborted PUT until EMFILE.
    close(fd);
    throw;
  }
  finish_upload(t, fd, total, t.mani ? hasher.hex() : std::string(), conn);
}

bool is_sha256_hex(const std::string& s) {
  if (s.size() != 64) return false;
  for (char c : s)
    if (!((c >= '0' && c <= '9') || (c >= 'a' && c <= 'f'))) return false;
  return true;
}

// Copies `size` bytes from src to dst at their file offsets, inside the
// kernel (which reflinks where the filesystem can); storage on another
// filesystem, or a kernel without the call, gets a plain read / write loop
// from wherever the copy stands.
bool copy_object(int src, int dst, long long size) {
  long long done = 0;
  bool in_kernel = true;
  std::vector<char> buf;
  while (done < size) {
    ssize_t n;
    if (in_kernel) {
      n = copy_file_range(src, nullptr, dst, nullptr,
                          static_cast<size_t>(size - done), 0);
      if (n < 0 && (errno == EXDEV || errno == ENOSYS || errno == EINVAL ||
                    errno == EOPNOTSUPP)) {
        in_kernel = false;
        buf.resize(1 << 20);
        continue;
      }
    } else {
      n = read(src, buf.data(), buf.size());
      if (n > 0 && !write_all(dst, buf.data(), static_cast<size_t>(n)))
        return false;
    }
    if (n < 0 && errno == EINTR) continue;
    if (n <= 0) return false;  // an error, or an object shorter than its stat
    done += n;
  }
  return true;
}

const char kCopyRoute[] = "/copy-from-storage";

// POST /copy-from-storage/workspace/<rel> with `x-storage-object: <sha256>`:
// an input file reaches the workspace as ONE copy inside the kernel from the
// storage object its sha names, where this server was spawned beside the
// storage directory — no byte through the control plane, none hashed here
// (the object's name IS the sha256 of its bytes: storage renames an object
// into place only when whole). A route of its own, so that a binary from
// before it answers 404 and the control plane keeps the PUT. The target is
// a FRESH inode, never a link: user code that writes an input in place
// cannot touch the object. Confinement, conditional skip, disk quota,
// manifest entry and reply are the PUT's.
void handle_copy_from_storage(const minihttp::Request& req, minihttp::Conn& conn) {
  conn.drain_body();
  if (g_state.storage_dir.empty() || !g_state.manifest_enabled) {
    conn.send_response(404, "application/json", "{\"error\":\"no route\"}");
    return;
  }
  UploadTarget t;
  if (!resolve_upload_target(req.target.substr(sizeof(kCopyRoute) - 1), conn, t))
    return;
  if (t.prefix != "workspace") {
    conn.send_response(404, "application/json", "{\"error\":\"unknown prefix\"}");
    return;
  }
  std::string id = req.header("x-storage-object");
  if (!is_sha256_hex(id)) {
    conn.send_response(400, "application/json", "{\"error\":\"bad object id\"}");
    return;
  }
  int src = open_confined(g_state.storage_dir, id, O_RDONLY, 0,
                          /*create_dirs=*/false);
  struct stat st;
  if (src < 0 || fstat(src, &st) != 0 || !S_ISREG(st.st_mode)) {
    if (src >= 0) close(src);
    conn.send_response(404, "application/json", "{\"error\":\"no such object\"}");
    return;
  }
  if (target_already_holds(t, id)) {
    close(src);
    conn.send_response(304, "application/json", "");
    return;
  }
  int fd = open_upload_target(t, conn);
  if (fd < 0) {
    close(src);
    return;
  }
  // The whole size is known before a byte is written.
  if (st.st_size > upload_quota_room(t)) {
    close(src);
    refuse_over_quota(t, fd, conn);
    return;
  }
  bool copied = copy_object(src, fd, st.st_size);
  close(src);
  if (!copied) {
    ftruncate(fd, 0);
    close(fd);
    conn.send_response(500, "application/json", "{\"error\":\"copy failed\"}");
    return;
  }
  finish_upload(t, fd, st.st_size, id, conn);
}

// GET /workspace-manifest — the resync surface: the full rel -> sha256 map
// of the workspace as it exists now (lazily rehashed). 404 when the
// manifest protocol is disabled, which is what an old binary answers too —
// the control plane treats both identically (full-transfer fallback).
void handle_manifest(const minihttp::Request&, minihttp::Conn& conn) {
  if (!g_state.manifest_enabled) {
    conn.send_response(404, "application/json", "{\"error\":\"no route\"}");
    return;
  }
  minijson::Object files;
  for (const auto& [rel, sha] :
       manifest_snapshot(g_state.workspace, g_ws_manifest, g_ws_manifest_mutex)) {
    files[rel] = minijson::Value(sha);
  }
  minijson::Object resp;
  resp["files"] = minijson::Value(files);
  conn.send_response(200, "application/json", minijson::Value(resp).dump());
}

// jax keeps 8-byte "-atime" sidecars beside each cache entry (its own
// local LRU bookkeeping, rewritten on every cache READ). They are per-host
// state with no fleet meaning and would churn the manifest on every hit —
// keep them out of the protocol entirely.
bool cc_entry_ignored(const std::string& rel) {
  static const std::string kSuffix = "-atime";
  return rel.size() >= kSuffix.size() &&
         rel.compare(rel.size() - kSuffix.size(), kSuffix.size(), kSuffix) == 0;
}

// GET /compile-cache-manifest — the fleet compile cache's negotiation
// surface: rel -> sha256 of every entry in the JAX compilation-cache dir
// (lazily rehashed, exactly like the workspace manifest). The control
// plane seeds against it at spawn (only missing entries cross the wire)
// and harvests against it at turnover (only never-seen entries come back).
// 404 when the compile cache is off — what an old binary answers too.
void handle_cc_manifest(const minihttp::Request&, minihttp::Conn& conn) {
  if (!g_state.compile_cache_enabled) {
    conn.send_response(404, "application/json", "{\"error\":\"no route\"}");
    return;
  }
  minijson::Object files;
  for (const auto& [rel, sha] : manifest_snapshot(
           g_state.compile_cache_dir, g_cc_manifest, g_cc_manifest_mutex)) {
    if (cc_entry_ignored(rel)) continue;
    files[rel] = minijson::Value(sha);
  }
  minijson::Object resp;
  resp["files"] = minijson::Value(files);
  conn.send_response(200, "application/json", minijson::Value(resp).dump());
}

void handle_download(const minihttp::Request& req, minihttp::Conn& conn) {
  std::string prefix, rel;
  if (!split_target(req.target, prefix, rel)) {
    conn.send_response(400, "application/json", "{\"error\":\"bad path\"}");
    return;
  }
  const std::string* base = prefix_base(prefix);
  if (!base) {
    conn.send_response(404, "application/json", "{\"error\":\"unknown prefix\"}");
    return;
  }
  int fd = open_confined(*base, rel, O_RDONLY, 0, /*create_dirs=*/false);
  if (fd < 0) {
    // Linux reports a refused symlink component as ELOOP (final) or ENOTDIR
    // (O_DIRECTORY|O_NOFOLLOW on an intermediate symlink).
    int status = errno == ELOOP || errno == ENOTDIR ? 403 : 404;
    conn.send_response(status, "application/json", "{\"error\":\"not found\"}");
    return;
  }
  if (!conn.send_file_fd(fd)) {  // closes fd
    conn.send_response(404, "application/json", "{\"error\":\"not a file\"}");
  }
}

void maybe_install_deps(const std::string& script_path) {
  if (!g_state.auto_install) return;
  std::string out_path = "/tmp/deps-out-" + std::to_string(getpid());
  ExecOutcome guess = run_subprocess(
      {g_state.python, g_state.deps_script, script_path, g_state.runtime_packages},
      "", out_path, "/dev/null", 30.0, nullptr);
  if (guess.exit_code != 0) return;
  std::string missing = read_file_capped(out_path, 1 << 16, nullptr);
  unlink(out_path.c_str());
  std::vector<std::string> pkgs;
  std::string cur;
  for (char c : missing + "\n") {
    if (c == '\n') {
      if (!cur.empty()) pkgs.push_back(cur);
      cur.clear();
    } else if (c != '\r') {
      cur += c;
    }
  }
  if (pkgs.empty()) return;
  std::vector<std::string> argv = {g_state.python, "-m", "pip", "install",
                                   "--no-cache-dir"};
  for (const auto& p : pkgs) argv.push_back(p);
  log_msg("auto-installing %zu missing deps", pkgs.size());
  run_subprocess(argv, "", "/dev/null", "/dev/null", 240.0, nullptr);
}

// Follows one capture file during a streaming execute, emitting
// {"stream":...,"data":...} NDJSON events for bytes appended since the last
// pump. Capped at `limit` bytes per stream (the final result object carries
// the truncation marker); the file may not exist yet on the first pumps.
class StreamTail {
 public:
  StreamTail(std::string path, std::string name, size_t limit)
      : path_(std::move(path)), name_(std::move(name)), limit_(limit) {}

  void pump(minihttp::Conn& conn) {
    if (sent_ >= limit_) return;
    int fd = ::open(path_.c_str(), O_RDONLY);
    if (fd < 0) return;  // not created yet
    if (lseek(fd, static_cast<off_t>(offset_), SEEK_SET) < 0) {
      ::close(fd);
      return;
    }
    char buf[1 << 16];
    std::string fresh;
    ssize_t n;
    while ((n = ::read(fd, buf, sizeof(buf))) > 0) {
      fresh.append(buf, static_cast<size_t>(n));
      if (offset_ + fresh.size() - sent_ > (1 << 20)) break;  // bounded batch
    }
    ::close(fd);
    if (fresh.empty()) return;
    // Never split a multi-byte UTF-8 character across two JSON events: the
    // client decodes each event's string independently, and a split
    // codepoint becomes U+FFFD on both sides. Hold incomplete trailing
    // bytes for the next pump (the final result body reads the raw file,
    // so nothing is ever lost to the hold-back).
    size_t emit_len = utf8_complete_prefix(fresh);
    if (emit_len == 0) return;
    fresh.resize(emit_len);
    offset_ += fresh.size();
    if (sent_ + fresh.size() > limit_) {
      fresh.resize(limit_ - sent_);
      fresh.resize(utf8_complete_prefix(fresh));  // cap edge, same rule
    }
    sent_ += fresh.size();
    if (fresh.empty()) return;
    minijson::Object event;
    event["stream"] = minijson::Value(name_);
    event["data"] = minijson::Value(fresh);
    conn.send_chunk(minijson::Value(event).dump() + "\n");
  }

  // Length of the longest prefix ending on a UTF-8 character boundary.
  // Invalid sequences (binary output) are passed through whole rather than
  // held forever: only a genuine incomplete multi-byte tail is trimmed.
  static size_t utf8_complete_prefix(const std::string& s) {
    if (s.empty()) return 0;
    size_t i = s.size();
    size_t back = 0;
    while (i > 0 && back < 4) {
      unsigned char c = static_cast<unsigned char>(s[i - 1]);
      if (c < 0x80) return s.size();  // ASCII tail: everything complete
      if ((c & 0xC0) == 0xC0) {
        // Lead byte at i-1 with `back` continuation bytes after it.
        size_t need = (c & 0xE0) == 0xC0   ? 1
                      : (c & 0xF0) == 0xE0 ? 2
                      : (c & 0xF8) == 0xF0 ? 3
                                           : 0;  // invalid lead: pass through
        if (need == 0 || need == back) return s.size();
        return need > back ? i - 1 : s.size();
      }
      --i;  // continuation byte, keep scanning back
      ++back;
    }
    return s.size();  // >=4 trailing continuation bytes: invalid, pass through
  }

 private:
  std::string path_;
  std::string name_;
  size_t limit_;
  size_t offset_ = 0;  // bytes consumed from the file
  size_t sent_ = 0;    // bytes emitted to the client (<= limit_)
};

// Outcome of one user-code run (warm runner or cold subprocess).
struct RunOutcome {
  int exit_code = -1;
  bool timed_out = false;
  bool runner_died = false;
  bool ran_warm = false;
  bool restarted = false;  // warm runner kill/crash -> background rewarm
  // No warm runner to serve the request, and a cold subprocess is not an
  // honest substitute: a multi-host slice only exists through the runner's
  // jax.distributed mesh, and a device-holding runner owns the chip.
  bool cold_refused = false;
  // Typed resource-limit violation ("" = none): which limit killed the run
  // (watchdog/rlimit) or fired in-process (the runner's soft guards).
  std::string violation;
  // Persistent-compilation-cache traffic observed by the warm runner's
  // jax.monitoring listener during this run (-1 = not reported: cold
  // subprocess, old runner, or jax without the monitoring surface).
  long long cache_hits = -1;
  long long cache_misses = -1;
  // Device-memory accounting block the warm runner sampled around the run
  // (live/peak device-buffer bytes + runner RSS) — present only when the
  // request asked for it AND the runner could measure (warm path; the cold
  // subprocess has no instrumented interpreter to sample).
  minijson::Value device_memory;
  // Stage clock of a warm run (mono_s(); 0 = no warm run): the request
  // line going into the runner's pipe, its reply line parsed, the guards
  // (watchdog thread, cgroup event read) down again. `runner_stages` is the
  // runner's own `stages` list as it sent it, offsets from `sent_mono`.
  double sent_mono = 0;
  double replied_mono = 0;
  double guards_down_mono = 0;
  minijson::Value runner_stages;
  // The numpy shim's counters of this run as the warm runner sent them
  // (programs, cache misses, nodes, flushes, bytes shipped and donated, host
  // seconds); absent from a runner without the shim and from a cold run.
  minijson::Value shim;
  // The runner's CPU seconds inside its `user_code` stage, as it sent them;
  // absent from a cold run.
  minijson::Value user_cpu_s;
};

// One entry of a `trace` block: a stage named `name`, nested in time (and,
// for a control plane that reads `parent`, in the tree) inside the entry
// called `parent`; offsets are seconds since the request's arrival.
void add_trace_span(minijson::Array& spans, const std::string& name,
                    double start_offset, double dur,
                    const char* parent = nullptr) {
  minijson::Object s;
  s["name"] = minijson::Value(name);
  s["start_offset_s"] = minijson::Value(start_offset);
  s["duration_s"] = minijson::Value(dur < 0 ? 0.0 : dur);
  if (parent) s["parent"] = minijson::Value(std::string(parent));
  spans.push_back(minijson::Value(s));
}

// The warm runner's `stages` ([name, start_offset_s, duration_s], offsets
// from the pipe write) forwarded as `runner.<name>` children of `parent`.
// Names are a fixed set: they end up as labels of the control plane's
// span histogram, and nothing the user's code could say may mint one.
void add_runner_stages(minijson::Array& spans, const minijson::Value& stages,
                       double sent_offset, const char* parent) {
  static const char* const kKnown[] = {
      "gc_after_reset", "pickup",        "prepare",      "profile_start",
      "limits_arm",     "user_code",     "limits_restore", "profile_stop",
      "finish",         "scrub"};
  if (!stages.is_array()) return;
  for (const auto& entry : stages.as_array()) {
    if (!entry.is_array()) continue;
    const auto& e = entry.as_array();
    if (e.size() != 3 || !e[0].is_string() || !e[1].is_number() ||
        !e[2].is_number())
      continue;
    for (const char* known : kKnown) {
      if (e[0].as_string() != known) continue;
      add_trace_span(spans, "runner." + e[0].as_string(),
                     sent_offset + e[1].as_number(), e[2].as_number(), parent);
      break;
    }
  }
}

// The execution core shared by /execute and /execute/stream: run the script
// through the warm runner when available, else a cold subprocess; stdout/
// stderr land in the given capture files (which is what makes streaming
// possible — a tailer can follow them while this blocks).
// The in-process guards the warm runner applies itself (runner.py): a JSON
// object for the runner request's `limits` key. Group-level bounds (nproc,
// disk, memory-as-RSS) are the watchdog's job and stay out.
minijson::Value runner_limits_json(const limits::LimitSpec& lim) {
  minijson::Object o;
  if (lim.memory_bytes > 0)
    o["memory_bytes"] = minijson::Value(static_cast<int64_t>(lim.memory_bytes));
  if (lim.cpu_seconds > 0) o["cpu_seconds"] = minijson::Value(lim.cpu_seconds);
  if (lim.nofile > 0)
    o["nofile"] = minijson::Value(static_cast<int64_t>(lim.nofile));
  if (lim.fsize_bytes > 0)
    o["fsize_bytes"] = minijson::Value(static_cast<int64_t>(lim.fsize_bytes));
  return minijson::Value(o);
}

// The 32-hex trace id inside a W3C traceparent ("00-<trace>-<span>-<fl>"),
// or "" — forwarded to the warm runner so its own log lines (and a batch
// job's) are attributable to the originating request.
std::string trace_id_of(const std::string& traceparent) {
  size_t a = traceparent.find('-');
  if (a == std::string::npos) return "";
  size_t b = traceparent.find('-', a + 1);
  if (b == std::string::npos || b - a != 33) return "";
  return traceparent.substr(a + 1, 32);
}

RunOutcome run_user_code(const std::string& script_path,
                         const std::string& stdout_path,
                         const std::string& stderr_path, double timeout_s,
                         const minijson::Value& extra_env,
                         const limits::LimitSpec& lim,
                         const std::string& trace_id = "",
                         bool want_device_memory = false) {
  RunOutcome out;
  bool restart_runner = false;

  // Pass 0 serves the request. Pass 1 exists only for a device-holding
  // runner found dead at request time: it is restarted, waited for, and the
  // request then runs warm on the new runner.
  for (int pass = 0;
       pass < 2 && g_state.warm_enabled && g_state.runner && !out.ran_warm;
       ++pass) {
    // Warm-up may still be in flight (the control plane normally gates on
    // /healthz warm before admitting a sandbox, but direct clients and
    // eager-mode pods can race it). Racing a cold subprocess against the
    // runner's TPU init would make both fight over the chip — wait it out.
    // Bounded: the warm thread resolves within the runner's ready timeout.
    // A RESTART in flight (g_ever_ready) of a runner that holds no device
    // is different: the previous request timed out, and the next one need
    // not pay re-init on its critical path — it falls through to the cold
    // subprocess immediately.
    {
      std::unique_lock<std::mutex> wl(g_warm_transition_mutex);
      g_warm_cv.wait(wl, [] {
        return g_warm_state.load() != kWarmPending ||
               (g_ever_ready.load() && !g_state.runner_holds_device);
      });
    }
    bool dead_at_request = false;
    bool restart_flagged_before = restart_runner;
    if (g_warm_state.load() == kWarmReady) {
      std::lock_guard<std::mutex> rlock(g_state.runner_mutex);
      if (g_state.runner->alive()) {
        minijson::Object reqo;
        reqo["source_path"] = minijson::Value(script_path);
        reqo["stdout_path"] = minijson::Value(stdout_path);
        reqo["stderr_path"] = minijson::Value(stderr_path);
        if (!trace_id.empty()) reqo["trace_id"] = minijson::Value(trace_id);
        if (want_device_memory) reqo["device_memory"] = minijson::Value(true);
        if (extra_env.is_object()) reqo["env"] = extra_env;
        if (lim.any()) reqo["limits"] = runner_limits_json(lim);
        minijson::Value resp;
        // Layered enforcement: the runner's in-process soft guards report
        // cleanly and keep the process (and its device lease) alive; the
        // watchdog is the backstop that kills the whole runner group when
        // user code dodges them (native allocs, children, masked signals).
        limits::Watchdog wd(lim, g_state.runner->pid(), g_state.workspace,
                            {stdout_path, stderr_path},
                            g_state.limit_poll_interval);
        wd.start();
        // Bracket the run with the runner scope's kernel event counters:
        // a memory.max OOM kill / pids.max fork refusal DURING this run
        // reclassifies a generic runner death below.
        g_runner_scope.refresh_baseline();
        out.sent_mono = mono_s();
        reqo["sent_mono"] = minijson::Value(out.sent_mono);
        WarmRunner::ExecResult r = g_state.runner->execute(
            minijson::Value(reqo).dump(), timeout_s > 0 ? timeout_s + 0.5 : 0,
            resp, /*allow_interrupt=*/true);
        out.replied_mono = mono_s();
        wd.stop();
        out.ran_warm = true;
        switch (r) {
          case WarmRunner::ExecResult::kOk:
            out.exit_code = static_cast<int>(resp.get_number("exit_code", -1));
            out.violation = resp.get_string("violation", "");
            out.cache_hits =
                static_cast<long long>(resp.get_number("cache_hits", -1));
            out.cache_misses =
                static_cast<long long>(resp.get_number("cache_misses", -1));
            out.device_memory = resp.get("device_memory");
            out.runner_stages = resp.get("stages");
            out.shim = resp.get("shim");
            out.user_cpu_s = resp.get("user_cpu_s");
            break;
          case WarmRunner::ExecResult::kTimeout:
            out.timed_out = true;
            restart_runner = true;
            break;
          case WarmRunner::ExecResult::kInterrupted:
            // Timed out, but cooperative cancellation unwound the user code
            // and the runner survived with its device lease AND state. No
            // scrub here: to a session the interrupt is just an exception
            // (its in-process state legitimately lives on, like any other
            // failed request), and pool turnover already resets between
            // tenants via /reset — an immediate scrub would silently break
            // the session contract while runner_restarted=false claims
            // state survived.
            out.timed_out = true;
            break;
          case WarmRunner::ExecResult::kDied:
            out.runner_died = true;
            restart_runner = true;
            break;
        }
        // A watchdog kill reaches the server as kDied/kTimeout (the runner
        // group is gone mid-request); the recorded kind reclassifies that
        // generic death as the typed violation it actually was. The
        // cgroup scope's event deltas do the same for KERNEL kills the
        // watchdog never saw coming (allocation bursts faster than one
        // sampling tick) — watchdog verdicts win when both fired.
        std::string wd_kind = wd.violation();
        if (!wd_kind.empty()) out.violation = wd_kind;
        if (out.violation.empty()) {
          const char* cg_kind = g_runner_scope.violation();
          if (cg_kind) out.violation = cg_kind;
        }
        out.guards_down_mono = mono_s();
      } else {
        // Runner found already dead at request time (e.g. OOM-killed
        // between requests): without flagging a restart here, the sandbox
        // would serve every subsequent request cold forever (sessions
        // never hit /reset, where dead-runner recovery otherwise lives)
        // and runner_restarted=false would hide the in-process state loss
        // from the control plane's session tracking. The request itself
        // runs via the cold path below — no stderr pollution — or, beside
        // a device holder, on the restarted runner (pass 1).
        restart_runner = dead_at_request = true;
      }
    }
    if (restart_runner && !restart_flagged_before) {
      // Restart in the background. Without a device to hold, this response
      // (and any request landing before the restart finishes) is served
      // cold, off the restart's critical path.
      g_warm_state = kWarmFailed;
      start_warm_async();
    }
    if (!(dead_at_request && g_state.runner_holds_device)) break;
  }
  out.restarted = restart_runner;

  if (!out.ran_warm) {
    if (g_state.num_hosts > 1 || g_state.runner_holds_device) {
      out.cold_refused = true;
      return out;
    }
    // launch.py wraps runpy with the same shell-syntax fallback the warm
    // runner applies (mixed Python/shell snippets — the xonsh role).
    // The cold child gets the real setrlimit set (it is wholly the user's)
    // plus the same watchdog backstop; the leader pid binds post-fork.
    limits::Watchdog wd(lim, 0, g_state.workspace, {stdout_path, stderr_path},
                        g_state.limit_poll_interval);
    wd.start();
    // Per-run cgroup scope (hard kernel backstop; throwaway). The memory
    // bound carries headroom above the watchdog's own slacked threshold —
    // the budget means "beyond baseline" and a cgroup counts from zero,
    // so the box must absorb the cold interpreter's startup RSS too; the
    // pids bound leaves room for the launch wrapper and interpreter
    // threads. Normal breaches still get the watchdog's clean typed kill;
    // the cgroup catches what outruns its sampling tick.
    cgroup::Scope run_scope;
    std::string run_procs;
    if (g_cgroup.enabled && (lim.memory_bytes > 0 || lim.nproc > 0)) {
      char scope_name[64];
      snprintf(scope_name, sizeof(scope_name), "run-%lld",
               static_cast<long long>(g_run_scope_seq.fetch_add(1) + 1));
      long long mem_headroom = lim.memory_bytes > (256LL << 20)
                                   ? lim.memory_bytes
                                   : (256LL << 20);
      run_scope = cgroup::Scope::create(
          g_cgroup, scope_name,
          lim.memory_bytes > 0 ? lim.memory_bytes + mem_headroom : 0,
          lim.nproc > 0 ? lim.nproc + 32 : 0);
      if (run_scope.active()) run_procs = run_scope.procs_path();
    }
    ExecOutcome cold = run_subprocess(
        {g_state.python, g_state.launch_script, script_path}, g_state.workspace,
        stdout_path, stderr_path, timeout_s, &extra_env, &lim, &wd,
        run_procs.empty() ? nullptr : &run_procs);
    wd.stop();
    out.exit_code = cold.exit_code;
    out.timed_out = cold.timed_out;
    out.violation = wd.violation();
    if (out.violation.empty() && lim.cpu_seconds > 0 &&
        cold.exit_code == 128 + SIGXCPU) {
      // RLIMIT_CPU fired in the child (no handler there): the kernel's
      // SIGXCPU kill IS the cpu_time violation.
      out.violation = limits::kCpuTime;
    }
    if (out.violation.empty()) {
      // Kernel-side enforcement evidence: an OOM kill at memory.max or a
      // fork refused at pids.max is the typed violation the generic exit
      // code hid.
      const char* cg_kind = run_scope.violation();
      if (cg_kind && (cold.exit_code != 0 || strcmp(cg_kind, limits::kOom) == 0))
        out.violation = cg_kind;
    }
    if (!run_scope.destroy()) {
      log_msg("cgroup scope %s would not die; leaking one empty dir",
              run_scope.dir().c_str());
    }
  }
  return out;
}

// POST /lease — record this sandbox's lease generation token. FIRST-WRITE-
// WINS for the process's lifetime: the control plane pushes exactly once,
// right after spawn and BEFORE the sandbox serves anything — so the only
// party that can ever land the first write is the control plane, and a
// later rotation attempt (tenant code curling localhost from inside the
// sandbox — this route is as reachable as /reset, but a forged rotation
// here would make the control plane's REAL token read stale and convert
// every request into an unbilled dispose-and-respawn) is refused with a
// 409. Re-posting the SAME token is an idempotent 200 (push retries).
void handle_lease(const minihttp::Request&, minihttp::Conn& conn) {
  std::string body = conn.read_body();
  std::string token;
  try {
    minijson::Value parsed = minijson::parse(body);
    token = parsed.get_string("token");
  } catch (const std::exception&) {
    conn.send_response(400, "application/json", "{\"error\":\"bad json\"}");
    return;
  }
  if (token.empty()) {
    conn.send_response(400, "application/json",
                       "{\"error\":\"token required\"}");
    return;
  }
  std::string conflict;
  {
    // Decide under the lock, respond outside it (never held across I/O).
    std::lock_guard<std::mutex> lock(g_lease_mutex);
    if (!g_lease_token.empty() && g_lease_token != token) {
      conflict = g_lease_token;
    } else {
      g_lease_token = token;
    }
  }
  if (!conflict.empty()) {
    log_msg("lease rotation refused: held=%s offered=%s", conflict.c_str(),
            token.c_str());
    // Held token log-only, like the dispatch refusals: a tenant POSTing a
    // bogus rotation from inside the sandbox must not be handed the real
    // credential in the refusal body.
    minijson::Object err;
    err["error"] = minijson::Value(std::string("lease_already_recorded"));
    conn.send_response(409, "application/json", minijson::Value(err).dump());
    return;
  }
  log_msg("lease token recorded: %s", token.c_str());
  minijson::Object resp;
  resp["ok"] = minijson::Value(true);
  resp["token"] = minijson::Value(token);
  conn.send_response(200, "application/json", minijson::Value(resp).dump());
}

// The fencing check: a request presenting a lease token that does not
// match the one this server holds is a claim minted for a fenced
// predecessor — refuse with the typed 409 and touch NOTHING (no mutex, no
// body parse, no device plane). Requests without the header (old control
// planes, manual curl) and servers without a recorded token (old control
// plane never POSTed /lease) pass through: enforcement is opt-in per hop,
// the control-plane revocation check is the backstop.
bool reject_stale_lease(const minihttp::Request& req, minihttp::Conn& conn) {
  std::string offered = req.header("x-lease-token");
  std::string held;
  {
    std::lock_guard<std::mutex> lock(g_lease_mutex);
    held = g_lease_token;
  }
  if (offered.empty()) {
    // Strict mode (APP_LEASE_REQUIRE_TOKEN=1): once a lease is recorded,
    // a tokenless dispatch is refused with its own typed 409 — on a
    // fully-rolled fleet every legitimate dispatch carries the token, so
    // "no token" can only be an old/foreign control plane or tenant code
    // curling the data plane from inside the sandbox. BEFORE any lease is
    // recorded, tokenless passes even in strict mode (boot-time probes,
    // the control plane's own pre-lease traffic).
    if (!g_state.lease_require_token || held.empty()) return false;
    log_msg("tokenless dispatch refused (strict lease mode; held=%s)",
            held.c_str());
    conn.drain_body();
    // The held token stays OUT of the body (log-only): this refusal is
    // exactly what tenant code curling the data plane from inside the
    // sandbox sees, and echoing the valid token would hand it the replay
    // credential the strict gate exists to demand.
    minijson::Object err;
    err["error"] = minijson::Value(std::string("lease_token_required"));
    conn.send_response(409, "application/json", minijson::Value(err).dump());
    return true;
  }
  if (held.empty() || offered == held) return false;
  log_msg("stale lease claim refused: offered=%s held=%s", offered.c_str(),
          held.c_str());
  conn.drain_body();
  minijson::Object err;
  err["error"] = minijson::Value(std::string("stale_lease"));
  // `offered` is the caller's own (stale) token — safe to echo for the
  // control plane's diagnostics. The HELD token is log-only: echoing the
  // successor's valid credential to whoever presented a stale one would
  // let any sandbox-internal caller harvest it with a junk claim.
  err["offered"] = minijson::Value(offered);
  conn.send_response(409, "application/json", minijson::Value(err).dump());
  return true;
}

// The canonical result hash for declared-pure runs: sha256 over stdout,
// stderr, the decimal exit code, and the SORTED changed-file content
// hashes, each part NUL-terminated. The control plane re-derives this from
// the very wire fields it received (result_content_sha in
// services/result_memo.py) and records nothing on a mismatch — the memo's
// end-to-end integrity check.
std::string pure_result_sha256(const std::string& out_s,
                               const std::string& err_s, int exit_code,
                               std::vector<std::string> file_shas) {
  std::sort(file_shas.begin(), file_shas.end());
  minisha::Sha256 h;
  auto part = [&h](const std::string& s) {
    h.update(s.data(), s.size());
    h.update("\0", 1);
  };
  part(out_s);
  part(err_s);
  part(std::to_string(exit_code));
  for (const auto& sha : file_shas) part(sha);
  return h.hex();
}

void handle_execute_impl(const minihttp::Request& req, minihttp::Conn& conn,
                         bool streaming) {
  // The request's arrival: the origin of every offset in the `trace` block.
  const double t_req = mono_s();
  auto since_req = [t_req]() { return mono_s() - t_req; };
  // Lease fencing FIRST: a stale claim must be refused before the body is
  // even read, and above all before exec_mutex — a wedged op may be
  // holding that lock for minutes, and a stale dispatch queueing behind it
  // is exactly the re-wedge this check exists to prevent.
  if (reject_stale_lease(req, conn)) return;
  // W3C trace context from the control plane: when present, the handler's
  // stages (parse, install, exec, collect and what each is made of, the
  // warm runner's own among them) are stamped into a `trace` block on the
  // response so the orchestrator can graft them into the request's trace
  // as child spans. Offsets are relative to this request's own start — the
  // two processes' clocks never have to agree.
  std::string traceparent = req.header("traceparent");

  std::string body = conn.read_body();
  minijson::Value parsed;
  try {
    parsed = minijson::parse(body);
  } catch (const std::exception& e) {
    conn.send_response(400, "application/json", "{\"error\":\"bad json\"}");
    return;
  }
  std::string source_code = parsed.get_string("source_code");
  std::string source_file = parsed.get_string("source_file");
  double timeout_s = parsed.get_number("timeout", g_state.default_timeout);
  // Per-request device-memory sampling (the perf-observer plane): only
  // requests that ASK get the runner bracket and the reply block, so the
  // control-plane kill switch keeps the wire byte-for-byte.
  bool want_device_memory = parsed.get_bool("device_memory", false);
  // Purity declaration (the control plane's result memo): echoed back with
  // a hashed result block so a record is verifiable end-to-end. Absent
  // unless declared — the memo kill switch keeps the wire byte-for-byte.
  bool declared_pure = parsed.get_bool("pure", false);
  const minijson::Value& extra_env = parsed.get("env");
  // Per-request resource budget, tighten-only against the APP_LIMIT_* caps.
  // Output is special-cased: the implicit server cap (APP_MAX_OUTPUT_BYTES)
  // keeps its historic TRUNCATE semantics; only an explicit output budget
  // (request body / control-plane lane default) arms the output-cap KILL.
  limits::LimitSpec req_limits = limits::from_json(parsed.get("limits"));
  limits::LimitSpec eff_limits = limits::clamp(req_limits, g_state.limit_caps);
  size_t output_cap = g_state.max_output;
  if (req_limits.output_bytes > 0 &&
      static_cast<size_t>(req_limits.output_bytes) < output_cap) {
    output_cap = static_cast<size_t>(req_limits.output_bytes);
  }
  eff_limits.output_bytes =
      req_limits.output_bytes > 0 ? static_cast<long long>(output_cap) : 0;

  if (source_code.empty() && source_file.empty()) {
    conn.send_response(400, "application/json",
                       "{\"error\":\"source_code or source_file required\"}");
    return;
  }

  std::lock_guard<std::mutex> lock(g_state.exec_mutex);

  // Per-request scratch dir: holds the script (source_code mode) and the
  // stdout/stderr capture files. Never inside the workspace — capture files
  // must not appear in the changed-file diff. Honors TMPDIR so sandboxes
  // with a private scratch tmp (local backend) keep everything inside it —
  // but an unwritable/missing TMPDIR (operator typo, container without the
  // mount) falls back to /tmp with a logged warning instead of failing
  // every request opaquely at mkdtemp.
  std::string tmpdir = env_or("TMPDIR", "/tmp");
  if (tmpdir != "/tmp" && access(tmpdir.c_str(), W_OK | X_OK) != 0) {
    log_msg("TMPDIR %s is not writable (%s); falling back to /tmp",
            tmpdir.c_str(), strerror(errno));
    tmpdir = "/tmp";
  }
  std::string tmpl_s = tmpdir + "/exec-XXXXXX";
  std::vector<char> tmpl(tmpl_s.begin(), tmpl_s.end());
  tmpl.push_back('\0');
  if (!mkdtemp(tmpl.data())) {
    int saved = errno;
    if (tmpdir != "/tmp") {
      // A last-resort retry: the writability probe can race a deletion, or
      // the filesystem can reject mkdtemp for reasons access() can't see.
      log_msg("mkdtemp in %s failed (%s); retrying under /tmp", tmpdir.c_str(),
              strerror(saved));
      tmpl_s = "/tmp/exec-XXXXXX";
      tmpl.assign(tmpl_s.begin(), tmpl_s.end());
      tmpl.push_back('\0');
    }
    if (tmpdir == "/tmp" || !mkdtemp(tmpl.data())) {
      saved = errno;
      minijson::Object err;
      err["error"] = minijson::Value(
          std::string("cannot create scratch dir under ") + tmpdir + ": " +
          strerror(saved) + " (check TMPDIR)");
      conn.send_response(500, "application/json",
                         minijson::Value(err).dump());
      return;
    }
  }
  std::string scratch(tmpl.data());
  std::string script_path;
  auto drop_scratch = [&scratch, &script_path]() {
    if (!script_path.empty()) unlink(script_path.c_str());
    rmdir(scratch.c_str());
  };
  if (!source_code.empty()) {
    script_path = scratch + "/script.py";
    if (!write_file(script_path, source_code)) {
      drop_scratch();
      conn.send_response(500, "application/json", "{\"error\":\"write failed\"}");
      return;
    }
  } else {
    std::string rel = sanitize_rel_path(source_file);
    std::string dup = "workspace/";
    if (rel.compare(0, dup.size(), dup) == 0) rel = rel.substr(dup.size());
    if (rel.empty() || !confine(g_state.workspace, rel, script_path)) {
      drop_scratch();
      conn.send_response(403, "application/json",
                         "{\"error\":\"source_file escapes workspace\"}");
      return;
    }
  }

  // Phase timings for the trace block: parse (everything above: lease
  // check, body, exec_mutex, scratch dir, script), install (dependency
  // auto-install + pre-exec snapshots, the latter its child scan_before),
  // exec (user code, between the guards going up and coming down), collect
  // (post-exec snapshot, output read, manifest reconcile, cache diff).
  double install_start = since_req();
  maybe_install_deps(script_path);

  double scan_before_start = since_req();
  std::map<std::string, FileSig> before;
  scan_dir(g_state.workspace, "", before);
  // Compile-cache observability: diff the cache dir across the run — new
  // entries are kernels THIS run had to compile (persistent-cache misses
  // made durable), which the control plane harvests and the fleet never
  // compiles again.
  std::map<std::string, FileSig> cc_before;
  if (g_state.compile_cache_enabled)
    scan_dir(g_state.compile_cache_dir, "", cc_before);

  std::string stdout_path = scratch + "/cap.stdout";
  std::string stderr_path = scratch + "/cap.stderr";

  double exec_start = since_req();

  RunOutcome run;
  if (!streaming) {
    run = run_user_code(script_path, stdout_path, stderr_path, timeout_s,
                        extra_env, eff_limits, trace_id_of(traceparent),
                        want_device_memory);
  } else {
    // Streaming mode: the run blocks in a worker thread while this thread
    // tails the capture files and pushes NDJSON events over a chunked
    // response. Events: {"stream":"stdout"|"stderr","data":...} chunks,
    // then one final result object (same fields as /execute's body).
    try {
      conn.begin_chunked(200, "application/x-ndjson");
    } catch (const std::exception&) {
      // Client vanished before headers: nothing to stream to. Clean the
      // scratch (submitted source may contain secrets) instead of letting
      // the throw unwind past it, then drop the connection.
      if (source_code.empty()) script_path.clear();  // workspace file: keep
      drop_scratch();
      throw;
    }
    std::atomic<bool> run_done{false};
    std::thread worker([&] {
      // A throw escaping a std::thread calls std::terminate — which would
      // take down the whole sandbox server (warm runner, sessions) for one
      // failed request. Degrade to a failed-run outcome instead, matching
      // the one-connection blast radius of the non-streaming path.
      try {
        run = run_user_code(script_path, stdout_path, stderr_path, timeout_s,
                            extra_env, eff_limits, trace_id_of(traceparent),
                            want_device_memory);
      } catch (const std::exception& e) {
        log_msg("streamed run_user_code threw: %s", e.what());
        run = RunOutcome{};  // exit_code -1, nothing ran warm
      }
      run_done.store(true);
    });
    StreamTail tail_out(stdout_path, "stdout", output_cap);
    StreamTail tail_err(stderr_path, "stderr", output_cap);
    bool client_gone = false;
    while (!run_done.load()) {
      struct timespec ts = {0, 75 * 1000 * 1000};  // 75 ms poll
      nanosleep(&ts, nullptr);
      if (client_gone) continue;  // keep draining the run; stop sending
      try {
        tail_out.pump(conn);
        tail_err.pump(conn);
      } catch (const std::exception&) {
        // Client went away mid-stream: the run must still complete (the
        // runner protocol would desync if we abandoned it mid-request).
        client_gone = true;
      }
    }
    worker.join();
    if (!client_gone) {
      try {
        tail_out.pump(conn);
        tail_err.pump(conn);
      } catch (const std::exception&) {
        client_gone = true;
      }
    }
    // client_gone: the epilogue still runs (scratch cleanup, runner state);
    // sending the final event will just fail silently in its try/catch.
  }

  if (run.cold_refused) {
    if (source_code.empty()) script_path.clear();  // workspace file: keep it
    drop_scratch();
    if (!streaming) {
      conn.send_response(500, "application/json",
                         "{\"error\":\"warm runner unavailable; refusing to "
                         "run beside the device holder\"}");
    } else {
      try {
        conn.send_chunk(
            "{\"error\":\"warm runner unavailable; refusing to run beside "
            "the device holder\"}\n");
        conn.end_chunked();
      } catch (const std::exception&) {
      }
    }
    return;
  }
  int exit_code = run.exit_code;
  bool timed_out = run.timed_out;
  bool runner_died = run.runner_died;
  bool ran_warm = run.ran_warm;
  bool restart_runner = run.restarted;

  double collect_start = since_req();
  double duration = collect_start - exec_start;

  std::map<std::string, FileSig> after;
  scan_dir(g_state.workspace, "", after);

  // Post-exec quota scan: a filler fast enough to write, exit, and beat the
  // watchdog's next tick still may not hand the next phase an over-quota
  // workspace (the downloads it would trigger are exactly the bytes the
  // quota exists to bound).
  if (run.violation.empty() && eff_limits.disk_bytes > 0 &&
      limits::dir_usage_bytes(g_state.workspace) > eff_limits.disk_bytes) {
    run.violation = limits::kDiskQuota;
  }
  double outputs_start = since_req();

  bool out_trunc = false, err_trunc = false;
  std::string out_s = read_file_capped(stdout_path, output_cap, &out_trunc);
  std::string err_s = read_file_capped(stderr_path, output_cap, &err_trunc);
  if (out_trunc) out_s += "\n[stdout truncated]";
  if (err_trunc) err_s += "\n[stderr truncated]";
  if (!run.violation.empty()) {
    std::string note = "Resource limit exceeded: " + run.violation;
    err_s += err_s.empty() ? note : "\n" + note;
  } else if (timed_out) {
    err_s += err_s.empty() ? "Execution timed out" : "\nExecution timed out";
  } else if (runner_died) {
    err_s += err_s.empty() ? "Executor runner crashed" : "\nExecutor runner crashed";
  }
  // Remove the scratch dir (submitted source may contain secrets, and a
  // long-lived dev server must not fill /tmp).
  unlink(stdout_path.c_str());
  unlink(stderr_path.c_str());
  if (source_code.empty()) script_path.clear();  // workspace file: keep it
  drop_scratch();

  minijson::Array files;
  minijson::Array deleted;
  std::vector<std::string> changed_file_shas;
  if (g_state.manifest_enabled) {
    // Changed files carry their content sha so the control plane can skip
    // downloading bytes its content-addressed storage already holds. The
    // manifest is reconciled in the same pass: changed entries rehash (the
    // mtime+size diff already singled them out — this is the "lazy" in lazy
    // rehash), gone entries drop and are reported in `deleted` so a cached
    // client manifest never claims a file the workspace lost.
    std::lock_guard<std::mutex> mlock(g_ws_manifest_mutex);
    for (auto it = g_ws_manifest.begin(); it != g_ws_manifest.end();) {
      if (after.find(it->first) == after.end()) {
        it = g_ws_manifest.erase(it);
      } else {
        ++it;
      }
    }
    for (const auto& rel : diff_snapshots(before, after)) {
      minijson::Object entry;
      entry["path"] = minijson::Value(rel);
      std::string hex;
      FileSig sig;
      if (hash_workspace_file(g_state.workspace, rel, hex, &sig)) {
        g_ws_manifest[rel] = ManifestEntry{hex, sig};
        entry["sha256"] = minijson::Value(hex);
        changed_file_shas.push_back(hex);
      }
      // Hash failure = the file vanished between scan and hash; the entry
      // still reports the path (sans sha) and the download path surfaces
      // the 404 exactly as the pre-manifest protocol did.
      files.push_back(minijson::Value(entry));
    }
    for (const auto& [rel, sig] : before) {
      if (after.find(rel) == after.end()) deleted.push_back(minijson::Value(rel));
    }
  } else {
    for (const auto& rel : diff_snapshots(before, after)) {
      files.push_back(minijson::Value(rel));
    }
  }

  minijson::Object resp;
  resp["stdout"] = minijson::Value(out_s);
  resp["stderr"] = minijson::Value(err_s);
  resp["exit_code"] = minijson::Value(exit_code);
  // Truncation is now a first-class signal (clients previously had to
  // pattern-match the "[stdout truncated]" text); violation carries the
  // typed limit kind when a resource bound ended this run.
  resp["stdout_truncated"] = minijson::Value(out_trunc);
  resp["stderr_truncated"] = minijson::Value(err_trunc);
  if (!run.violation.empty()) resp["violation"] = minijson::Value(run.violation);
  resp["files"] = minijson::Value(files);
  if (g_state.manifest_enabled) resp["deleted"] = minijson::Value(deleted);
  double cache_scan_start = since_req();
  if (g_state.compile_cache_enabled) {
    std::map<std::string, FileSig> cc_after;
    scan_dir(g_state.compile_cache_dir, "", cc_after);
    long long new_entries = 0, new_bytes = 0;
    for (const auto& [rel, sig] : cc_after) {
      if (cc_entry_ignored(rel)) continue;  // jax's local -atime sidecars
      auto it = cc_before.find(rel);
      if (it == cc_before.end() || !(it->second == sig)) {
        ++new_entries;
        new_bytes += sig.size;
      }
    }
    minijson::Object cc;
    cc["new_entries"] = minijson::Value(static_cast<int64_t>(new_entries));
    cc["new_bytes"] = minijson::Value(static_cast<int64_t>(new_bytes));
    cc["entries"] = minijson::Value(static_cast<int64_t>(cc_after.size()));
    if (run.cache_hits >= 0)
      cc["hits"] = minijson::Value(static_cast<int64_t>(run.cache_hits));
    if (run.cache_misses >= 0)
      cc["misses"] = minijson::Value(static_cast<int64_t>(run.cache_misses));
    resp["compile_cache"] = minijson::Value(cc);
  }
  resp["duration_s"] = minijson::Value(duration);
  // The request's device-op wall (the op window around the warm-runner
  // round-trip / cold subprocess): the control plane's chip-second
  // attribution source. Named explicitly so the billing contract does not
  // lean on duration_s keeping its exact semantics forever.
  resp["device_op_seconds"] = minijson::Value(duration);
  // Device-memory accounting (present only when requested AND the warm
  // runner could sample): live/peak device-buffer bytes bracketing the
  // run, plus the runner's RSS — the per-request HBM attribution feed.
  if (run.device_memory.is_object())
    resp["device_memory"] = run.device_memory;
  // The numpy shim's counters, forwarded as sent: the control plane reads
  // the names it knows, as numbers, and nothing else.
  if (run.shim.is_object()) resp["shim"] = run.shim;
  if (run.user_cpu_s.is_number()) resp["user_cpu_s"] = run.user_cpu_s;
  resp["warm"] = minijson::Value(ran_warm);
  // True when the warm runner was killed (timeout) or died during this
  // request: its in-process state is gone and a rewarm is in flight. The
  // control plane uses this to end executor_id sessions, whose contract is
  // that the process persists across requests.
  resp["runner_restarted"] = minijson::Value(restart_runner);
  if (declared_pure) {
    resp["pure"] = minijson::Value(true);
    resp["result_sha256"] = minijson::Value(
        pure_result_sha256(out_s, err_s, exit_code, changed_file_shas));
  }
  if (!traceparent.empty()) {
    // The control plane sent trace context: report per-stage timings so it
    // can graft them into the request's trace as child spans. Offsets are
    // seconds since THIS request started on this host (the grafter anchors
    // them to its own span start — no cross-process clock agreement). The
    // block is the last thing written into the reply, so `total_s` holds
    // everything the handler did but serialise and send it.
    double block_at = since_req();
    minijson::Object trace;
    trace["traceparent"] = minijson::Value(traceparent);
    minijson::Array trace_spans;
    add_trace_span(trace_spans, "parse", 0.0, install_start);
    add_trace_span(trace_spans, "install", install_start,
                   exec_start - install_start);
    add_trace_span(trace_spans, "scan_before", scan_before_start,
                   exec_start - scan_before_start, "install");
    add_trace_span(trace_spans, "exec", exec_start, duration);
    if (run.sent_mono > 0) {
      double sent = run.sent_mono - t_req;
      double replied = run.replied_mono - t_req;
      add_trace_span(trace_spans, "guard_start", exec_start, sent - exec_start,
                     "exec");
      add_trace_span(trace_spans, "runner_wait", sent, replied - sent, "exec");
      add_runner_stages(trace_spans, run.runner_stages, sent, "exec");
      add_trace_span(trace_spans, "guard_stop", replied,
                     run.guards_down_mono - run.replied_mono, "exec");
    }
    add_trace_span(trace_spans, "collect", collect_start,
                   block_at - collect_start);
    add_trace_span(trace_spans, "scan_after", collect_start,
                   outputs_start - collect_start, "collect");
    add_trace_span(trace_spans, "outputs", outputs_start,
                   cache_scan_start - outputs_start, "collect");
    add_trace_span(trace_spans, "cache_scan", cache_scan_start,
                   block_at - cache_scan_start, "collect");
    trace["spans"] = minijson::Value(trace_spans);
    trace["total_s"] = minijson::Value(block_at);
    resp["trace"] = minijson::Value(trace);
  }
  if (!streaming) {
    conn.send_response(200, "application/json", minijson::Value(resp).dump());
  } else {
    // Final event: the complete /execute response body (chunks were purely
    // additive), so a streaming client needs no second code path to build
    // the result. A vanished client just misses it.
    try {
      conn.send_chunk(minijson::Value(resp).dump() + "\n");
      conn.end_chunked();
    } catch (const std::exception&) {
    }
  }
}

void handle_execute(const minihttp::Request& req, minihttp::Conn& conn) {
  handle_execute_impl(req, conn, /*streaming=*/false);
}

void handle_execute_stream(const minihttp::Request& req,
                           minihttp::Conn& conn) {
  handle_execute_impl(req, conn, /*streaming=*/true);
}

// Monotonic batch-staging counter: each batch's per-job workdirs live under
// a fresh workspace-relative ".batch-<n>" root (exec_mutex serializes
// batches, but a previous batch's dirs persist until /reset — reusing a
// name would make its leftovers look like the new batch's output).
std::atomic<long> g_batch_seq{0};

// POST /execute-batch — the fused half of batched multi-chip execution
// lanes: N compatible small jobs staged into per-job workdirs and run as
// ONE warm-runner dispatch whose job threads spread over the local device
// axis. Per-job stdout/stderr/exit/violation/files come back demuxed; any
// refusal (no warm runner, multi-host slice, old binary's 404) tells the
// control plane to fall back to the serial path.
void handle_execute_batch(const minihttp::Request& req, minihttp::Conn& conn) {
  // Same fencing discipline as /execute: stale claims die before the body
  // read and before exec_mutex.
  if (reject_stale_lease(req, conn)) return;
  std::string traceparent = req.header("traceparent");
  struct timespec t_req;
  clock_gettime(CLOCK_MONOTONIC, &t_req);
  auto since_req = [&t_req]() {
    struct timespec now;
    clock_gettime(CLOCK_MONOTONIC, &now);
    return (now.tv_sec - t_req.tv_sec) + (now.tv_nsec - t_req.tv_nsec) / 1e9;
  };

  std::string body = conn.read_body();
  minijson::Value parsed;
  try {
    parsed = minijson::parse(body);
  } catch (const std::exception&) {
    conn.send_response(400, "application/json", "{\"error\":\"bad json\"}");
    return;
  }
  const minijson::Value& jobs_v = parsed.get("jobs");
  if (!jobs_v.is_array() || jobs_v.as_array().empty() ||
      jobs_v.as_array().size() > 64) {
    conn.send_response(400, "application/json",
                       "{\"error\":\"jobs must be a non-empty array "
                       "(max 64)\"}");
    return;
  }
  const minijson::Array& jobs = jobs_v.as_array();
  for (const auto& job : jobs) {
    if (job.get_string("source_code").empty()) {
      conn.send_response(400, "application/json",
                         "{\"error\":\"every batch job needs source_code\"}");
      return;
    }
  }
  if (g_state.num_hosts > 1) {
    // A multi-host slice's mesh spans executors; the fused driver runs on
    // one host's runner. The control plane never sends this — refuse
    // loudly rather than run jobs against a silently partial mesh.
    conn.send_response(409, "application/json",
                       "{\"error\":\"batch dispatch unsupported on a "
                       "multi-host slice\"}");
    return;
  }
  if (!g_state.warm_enabled || !g_state.runner) {
    conn.send_response(409, "application/json",
                       "{\"error\":\"batch dispatch requires the warm "
                       "runner\"}");
    return;
  }
  double timeout_s = parsed.get_number("timeout", g_state.default_timeout);
  bool want_device_memory = parsed.get_bool("device_memory", false);
  const minijson::Value& extra_env = parsed.get("env");
  // Same output special-casing as /execute: the implicit server cap keeps
  // TRUNCATE semantics; only an explicit output budget arms the watchdog's
  // output-cap KILL (batch-level, like every other fused-run bound).
  limits::LimitSpec req_limits = limits::from_json(parsed.get("limits"));
  limits::LimitSpec eff_limits = limits::clamp(req_limits, g_state.limit_caps);
  size_t output_cap = g_state.max_output;
  if (req_limits.output_bytes > 0 &&
      static_cast<size_t>(req_limits.output_bytes) < output_cap) {
    output_cap = static_cast<size_t>(req_limits.output_bytes);
  }
  eff_limits.output_bytes =
      req_limits.output_bytes > 0 ? static_cast<long long>(output_cap) : 0;

  std::lock_guard<std::mutex> lock(g_state.exec_mutex);

  // Scratch (scripts + capture files) and the workspace-relative staging
  // root holding one PRIVATE workdir per job — the demux unit for changed
  // files. Same TMPDIR fallback discipline as /execute.
  std::string tmpdir = env_or("TMPDIR", "/tmp");
  if (tmpdir != "/tmp" && access(tmpdir.c_str(), W_OK | X_OK) != 0) tmpdir = "/tmp";
  std::string tmpl_s = tmpdir + "/exec-batch-XXXXXX";
  std::vector<char> tmpl(tmpl_s.begin(), tmpl_s.end());
  tmpl.push_back('\0');
  if (!mkdtemp(tmpl.data())) {
    conn.send_response(500, "application/json",
                       "{\"error\":\"cannot create batch scratch dir\"}");
    return;
  }
  std::string scratch(tmpl.data());
  std::string batch_rel = ".batch-" + std::to_string(++g_batch_seq);
  std::string batch_root = g_state.workspace + "/" + batch_rel;
  std::vector<std::string> cleanup_files;
  auto fail = [&](int status, const std::string& message) {
    for (const auto& path : cleanup_files) unlink(path.c_str());
    rmdir(scratch.c_str());
    minijson::Object err;
    err["error"] = minijson::Value(message);
    conn.send_response(status, "application/json",
                       minijson::Value(err).dump());
  };
  if (mkdir(batch_root.c_str(), 0755) != 0) {
    fail(500, "cannot create batch staging root");
    return;
  }

  double install_start = since_req();
  minijson::Array runner_jobs;
  std::vector<std::string> job_rels, job_out_paths, job_err_paths;
  for (size_t i = 0; i < jobs.size(); ++i) {
    std::string job_rel = batch_rel + "/job-" + std::to_string(i);
    std::string job_dir = g_state.workspace + "/" + job_rel;
    if (mkdir(job_dir.c_str(), 0755) != 0) {
      fail(500, "cannot create batch job workdir");
      return;
    }
    std::string script_path = scratch + "/job-" + std::to_string(i) + ".py";
    if (!write_file(script_path, jobs[i].get_string("source_code"))) {
      fail(500, "cannot stage batch job script");
      return;
    }
    cleanup_files.push_back(script_path);
    maybe_install_deps(script_path);
    std::string out_path = scratch + "/job-" + std::to_string(i) + ".stdout";
    std::string err_path = scratch + "/job-" + std::to_string(i) + ".stderr";
    job_rels.push_back(job_rel);
    job_out_paths.push_back(out_path);
    job_err_paths.push_back(err_path);
    cleanup_files.push_back(out_path);
    cleanup_files.push_back(err_path);
    minijson::Object rj;
    rj["source_path"] = minijson::Value(script_path);
    rj["stdout_path"] = minijson::Value(out_path);
    rj["stderr_path"] = minijson::Value(err_path);
    rj["cwd"] = minijson::Value(job_dir);
    std::string job_trace = jobs[i].get_string("trace_id");
    if (!job_trace.empty()) rj["trace_id"] = minijson::Value(job_trace);
    const minijson::Value& device = jobs[i].get("device_index");
    if (device.is_number()) rj["device_index"] = device;
    runner_jobs.push_back(minijson::Value(rj));
  }
  std::map<std::string, FileSig> cc_before;
  if (g_state.compile_cache_enabled)
    scan_dir(g_state.compile_cache_dir, "", cc_before);
  double install_s = since_req() - install_start;

  std::string batch_out = scratch + "/batch.stdout";
  std::string batch_err = scratch + "/batch.stderr";
  cleanup_files.push_back(batch_out);
  cleanup_files.push_back(batch_err);

  minijson::Object reqo;
  reqo["op"] = minijson::Value(std::string("batch"));
  reqo["jobs"] = minijson::Value(runner_jobs);
  reqo["stdout_path"] = minijson::Value(batch_out);
  reqo["stderr_path"] = minijson::Value(batch_err);
  std::string trace_id = trace_id_of(traceparent);
  if (!trace_id.empty()) reqo["trace_id"] = minijson::Value(trace_id);
  if (want_device_memory) reqo["device_memory"] = minijson::Value(true);
  if (extra_env.is_object()) reqo["env"] = extra_env;
  if (eff_limits.any()) reqo["limits"] = runner_limits_json(eff_limits);

  double exec_start = since_req();
  bool timed_out = false, runner_died = false, ran_warm = false;
  bool restart_runner = false;
  std::string batch_violation;
  minijson::Value runner_resp;
  long long cache_hits = -1, cache_misses = -1;
  {
    // Same warm-up wait discipline as run_user_code; but a batch NEVER
    // falls back to a cold subprocess — there is no per-job isolation
    // story there, and the control plane's serial fallback is strictly
    // better.
    {
      std::unique_lock<std::mutex> wl(g_warm_transition_mutex);
      g_warm_cv.wait(wl, [] {
        return g_warm_state.load() != kWarmPending || g_ever_ready.load();
      });
    }
    if (g_warm_state.load() != kWarmReady) {
      fail(409, "warm runner not ready for batch dispatch");
      return;
    }
    std::lock_guard<std::mutex> rlock(g_state.runner_mutex);
    if (!g_state.runner->alive()) {
      g_warm_state = kWarmFailed;
      start_warm_async();
      fail(409, "warm runner not alive for batch dispatch");
      return;
    }
    // The watchdog watches EVERY capture file of the fused run: each job's
    // private stdout/stderr (where the per-thread stream demux routes
    // Python-level output) plus the batch-level pair (fd-level writes). An
    // explicit output budget is a batch-level bound over their sum, like
    // cpu_time — the serial rerun after an output_cap kill gives the real
    // offender its individual verdict.
    std::vector<std::string> capture_paths = job_out_paths;
    capture_paths.insert(capture_paths.end(), job_err_paths.begin(),
                         job_err_paths.end());
    capture_paths.push_back(batch_out);
    capture_paths.push_back(batch_err);
    limits::Watchdog wd(eff_limits, g_state.runner->pid(), g_state.workspace,
                        capture_paths, g_state.limit_poll_interval);
    wd.start();
    // Same kernel-event bracket as the serial warm path: a cgroup OOM/
    // fork-refusal during the fused run is a BATCH-level violation (the
    // group is shared), reclassified below.
    g_runner_scope.refresh_baseline();
    WarmRunner::ExecResult r = g_state.runner->execute(
        minijson::Value(reqo).dump(), timeout_s > 0 ? timeout_s + 0.5 : 0,
        runner_resp, /*allow_interrupt=*/true);
    wd.stop();
    ran_warm = true;
    switch (r) {
      case WarmRunner::ExecResult::kOk:
        batch_violation = runner_resp.get_string("violation", "");
        cache_hits =
            static_cast<long long>(runner_resp.get_number("cache_hits", -1));
        cache_misses =
            static_cast<long long>(runner_resp.get_number("cache_misses", -1));
        break;
      case WarmRunner::ExecResult::kTimeout:
        timed_out = true;
        restart_runner = true;
        break;
      case WarmRunner::ExecResult::kInterrupted:
        // The runner survived the SIGINT, but its job THREADS may not have
        // unwound (signals reach only the main thread) — the next /reset
        // will refuse on surviving threads and the control plane disposes.
        timed_out = true;
        break;
      case WarmRunner::ExecResult::kDied:
        runner_died = true;
        restart_runner = true;
        break;
    }
    std::string wd_kind = wd.violation();
    if (!wd_kind.empty()) batch_violation = wd_kind;
    if (batch_violation.empty()) {
      const char* cg_kind = g_runner_scope.violation();
      if (cg_kind) batch_violation = cg_kind;
    }
    if (restart_runner) {
      g_warm_state = kWarmFailed;
      start_warm_async();
    }
  }
  double exec_s = since_req() - exec_start;

  // Post-exec disk-quota scan over the whole workspace (the batch root is
  // inside it), batch-level like every other group bound.
  if (batch_violation.empty() && eff_limits.disk_bytes > 0 &&
      limits::dir_usage_bytes(g_state.workspace) > eff_limits.disk_bytes) {
    batch_violation = limits::kDiskQuota;
  }

  double collect_start = since_req();
  const minijson::Value& job_results = runner_resp.get("jobs");
  minijson::Array results;
  minijson::Array trace_spans;
  for (size_t i = 0; i < jobs.size(); ++i) {
    minijson::Object entry;
    entry["workdir"] = minijson::Value(job_rels[i]);
    int exit_code = -1;
    double job_duration = 0.0, job_offset = 0.0;
    std::string job_violation;
    bool aborted = timed_out || runner_died;
    if (job_results.is_array() && i < job_results.as_array().size()) {
      const minijson::Value& jr = job_results.as_array()[i];
      exit_code = static_cast<int>(jr.get_number("exit_code", -1));
      job_duration = jr.get_number("duration_s", 0.0);
      job_offset = jr.get_number("start_offset_s", 0.0);
      job_violation = jr.get_string("violation", "");
      aborted = aborted || jr.get_bool("aborted", false);
      // Per-job device-memory bracket (best-effort under concurrent
      // batchmates — one address space; the wire shape matches /execute's
      // block so the demux path parses once).
      if (jr.get("device_memory").is_object())
        entry["device_memory"] = jr.get("device_memory");
    }
    bool out_trunc = false, err_trunc = false;
    std::string out_s =
        read_file_capped(job_out_paths[i], output_cap, &out_trunc);
    std::string err_s =
        read_file_capped(job_err_paths[i], output_cap, &err_trunc);
    if (out_trunc) out_s += "\n[stdout truncated]";
    if (err_trunc) err_s += "\n[stderr truncated]";
    if (!job_violation.empty()) {
      std::string note = "Resource limit exceeded: " + job_violation;
      err_s += err_s.empty() ? note : "\n" + note;
    }
    entry["stdout"] = minijson::Value(out_s);
    entry["stderr"] = minijson::Value(err_s);
    entry["exit_code"] = minijson::Value(exit_code);
    entry["stdout_truncated"] = minijson::Value(out_trunc);
    entry["stderr_truncated"] = minijson::Value(err_trunc);
    entry["duration_s"] = minijson::Value(job_duration);
    // Per-job device-op seconds: the job thread's own exec span inside the
    // fused run — the weight the control plane apportions the dispatch's
    // chip-seconds by (usage metering; duplicates duration_s today, named
    // separately so the attribution contract survives if duration_s ever
    // grows non-device phases).
    entry["device_op_seconds"] = minijson::Value(job_duration);
    entry["start_offset_s"] = minijson::Value(exec_start + job_offset);
    if (!job_violation.empty())
      entry["violation"] = minijson::Value(job_violation);
    if (aborted) entry["aborted"] = minijson::Value(true);
    // Changed files = everything in the job's private workdir (created
    // fresh for this batch), reported RELATIVE to it so the control plane
    // can demux each caller's files to the paths its code wrote.
    minijson::Array files;
    std::vector<std::string> job_file_shas;
    std::map<std::string, FileSig> job_files;
    scan_dir(g_state.workspace + "/" + job_rels[i], "", job_files);
    for (const auto& [rel, sig] : job_files) {
      minijson::Object fe;
      fe["path"] = minijson::Value(rel);
      if (g_state.manifest_enabled) {
        std::string full_rel = job_rels[i] + "/" + rel;
        std::string hex;
        FileSig hashed;
        if (hash_workspace_file(g_state.workspace, full_rel, hex, &hashed)) {
          std::lock_guard<std::mutex> mlock(g_ws_manifest_mutex);
          g_ws_manifest[full_rel] = ManifestEntry{hex, hashed};
          fe["sha256"] = minijson::Value(hex);
          job_file_shas.push_back(hex);
        }
      }
      files.push_back(minijson::Value(fe));
    }
    entry["files"] = minijson::Value(files);
    if (jobs[i].get_bool("pure", false)) {
      // Per-job purity echo, hashed over THIS entry's demuxed streams and
      // files — a batchmate's output can never slip into a recorded
      // result unnoticed.
      entry["pure"] = minijson::Value(true);
      entry["result_sha256"] = minijson::Value(
          pure_result_sha256(out_s, err_s, exit_code, job_file_shas));
    }
    results.push_back(minijson::Value(entry));
    if (!traceparent.empty()) {
      minijson::Object s;
      s["name"] = minijson::Value("job-" + std::to_string(i));
      s["start_offset_s"] = minijson::Value(exec_start + job_offset);
      s["duration_s"] = minijson::Value(job_duration);
      trace_spans.push_back(minijson::Value(s));
    }
  }
  // Read the batch-level captures BEFORE the scratch cleanup unlinks them.
  // Batch-level STDOUT means fd-level writes (a subprocess, a C extension)
  // bypassed the per-thread demux: surface it so the control plane can
  // refuse the demux and rerun serially — output the serial path returns
  // must never be silently dropped.
  bool stray_trunc = false;
  std::string stray_err = read_file_capped(batch_err, 64 * 1024, &stray_trunc);
  std::string stray_out = read_file_capped(batch_out, 64 * 1024, &stray_trunc);
  for (const auto& path : cleanup_files) unlink(path.c_str());
  rmdir(scratch.c_str());

  minijson::Object resp;
  resp["results"] = minijson::Value(results);
  resp["warm"] = minijson::Value(ran_warm);
  resp["runner_restarted"] = minijson::Value(restart_runner);
  // The fused dispatch's device-op wall, from this server's own op window
  // (the whole runner round-trip): what the batch actually held the
  // devices for — the control plane's chip-second attribution source
  // (per-job shares are apportioned by the entries' device_op_seconds).
  resp["device_op_seconds"] = minijson::Value(exec_s);
  if (timed_out) resp["timed_out"] = minijson::Value(true);
  if (!batch_violation.empty())
    resp["violation"] = minijson::Value(batch_violation);
  if (!stray_err.empty()) resp["batch_stderr"] = minijson::Value(stray_err);
  if (!stray_out.empty()) resp["batch_stdout"] = minijson::Value(stray_out);
  if (g_state.compile_cache_enabled) {
    std::map<std::string, FileSig> cc_after;
    scan_dir(g_state.compile_cache_dir, "", cc_after);
    long long new_entries = 0, new_bytes = 0;
    for (const auto& [rel, sig] : cc_after) {
      if (cc_entry_ignored(rel)) continue;
      auto it = cc_before.find(rel);
      if (it == cc_before.end() || !(it->second == sig)) {
        ++new_entries;
        new_bytes += sig.size;
      }
    }
    minijson::Object cc;
    cc["new_entries"] = minijson::Value(static_cast<int64_t>(new_entries));
    cc["new_bytes"] = minijson::Value(static_cast<int64_t>(new_bytes));
    cc["entries"] = minijson::Value(static_cast<int64_t>(cc_after.size()));
    if (cache_hits >= 0)
      cc["hits"] = minijson::Value(static_cast<int64_t>(cache_hits));
    if (cache_misses >= 0)
      cc["misses"] = minijson::Value(static_cast<int64_t>(cache_misses));
    resp["compile_cache"] = minijson::Value(cc);
  }
  if (!traceparent.empty()) {
    double collect_s = since_req() - collect_start;
    minijson::Object trace;
    trace["traceparent"] = minijson::Value(traceparent);
    minijson::Object s_install;
    s_install["name"] = minijson::Value(std::string("install"));
    s_install["start_offset_s"] = minijson::Value(install_start);
    s_install["duration_s"] = minijson::Value(install_s);
    trace_spans.push_back(minijson::Value(s_install));
    minijson::Object s_exec;
    s_exec["name"] = minijson::Value(std::string("exec"));
    s_exec["start_offset_s"] = minijson::Value(exec_start);
    s_exec["duration_s"] = minijson::Value(exec_s);
    trace_spans.push_back(minijson::Value(s_exec));
    minijson::Object s_collect;
    s_collect["name"] = minijson::Value(std::string("collect"));
    s_collect["start_offset_s"] = minijson::Value(collect_start);
    s_collect["duration_s"] = minijson::Value(collect_s);
    trace_spans.push_back(minijson::Value(s_collect));
    trace["spans"] = minijson::Value(trace_spans);
    resp["trace"] = minijson::Value(trace);
  }
  conn.send_response(200, "application/json", minijson::Value(resp).dump());
}

minijson::Value warm_status_body() {
  minijson::Object resp;
  resp["status"] = minijson::Value("ok");
  int state = g_warm_state.load();
  bool warm = state == kWarmReady && g_state.runner && g_state.runner->alive();
  resp["warm"] = minijson::Value(warm);
  resp["warm_state"] = minijson::Value(std::string(warm_state_name(state)));
  if (warm) {
    resp["backend"] = minijson::Value(g_state.runner->backend());
    resp["device_count"] = minijson::Value(g_state.runner->device_count());
  }
  // Which limits-enforcement mode this sandbox ACTUALLY runs in: cgroup-v2
  // hard caps (memory.max/pids.max armed), or the rlimits+watchdog
  // fallback and why. The control plane, operators, and the test suite's
  // auto-skip all read this instead of guessing at the host's cgroup
  // posture.
  {
    minijson::Object cg;
    cg["enforced"] = minijson::Value(g_cgroup.enabled);
    if (g_cgroup.enabled) {
      cg["base"] = minijson::Value(g_cgroup.base);
      cg["runner_scope"] = minijson::Value(g_runner_scope.active());
    } else {
      cg["fallback_reason"] = minijson::Value(g_cgroup.reason);
    }
    resp["cgroup"] = minijson::Value(cg);
  }
  return minijson::Value(resp);
}

void handle_healthz(const minihttp::Request&, minihttp::Conn& conn) {
  // Liveness + warm telemetry: always 200 while the server is up; the body
  // carries warm_state so the control plane can poll init progress.
  conn.send_response(200, "application/json", warm_status_body().dump());
}

// GET /device-stats — the raw device-health signals the control plane's
// probe daemon classifies into healthy/busy/suspect/wedged. DELIBERATELY
// lock-free (atomics + one tiny string mutex never held across I/O): it
// must answer while exec_mutex/runner_mutex are pinned by a wedged device
// op — the exact situation where /healthz kept saying "ok" while attaches
// never completed (rounds 3 to 5). Ages are computed server-side on
// the server's own monotonic clock, so the probe never does cross-host
// clock math.
void handle_device_stats(const minihttp::Request&, minihttp::Conn& conn) {
  long long now = now_ms();
  minijson::Object resp;
  resp["status"] = minijson::Value(std::string("ok"));
  int state = g_warm_state.load();
  resp["warm_state"] = minijson::Value(std::string(warm_state_name(state)));
  resp["warm"] =
      minijson::Value(state == kWarmReady && g_runner_ready_stat.load());
  {
    std::lock_guard<std::mutex> dlock(g_device_info_mutex);
    resp["backend"] = minijson::Value(g_device_backend_stat);
    resp["device_kind"] = minijson::Value(g_device_kind_stat);
    // The last attach's steps as the runner timed them, beside
    // `attach_seconds` below (which also holds the spawn and the ready line).
    if (g_attach_stages_stat.is_object())
      resp["attach_stages"] = g_attach_stages_stat;
  }
  resp["device_count"] = minijson::Value(g_device_count_stat.load());
  resp["num_hosts"] = minijson::Value(g_state.num_hosts);
  resp["uptime_s"] = minijson::Value((now - g_boot_ms.load()) / 1000.0);
  // Attach telemetry: pending age while a warm-up (jax import + device
  // attach) is in flight, plus the last successful attach's latency.
  long long attach_start = g_attach_start_ms.load();
  resp["attach_pending_s"] = minijson::Value(
      attach_start > 0 ? (now - attach_start) / 1000.0 : 0.0);
  long long attach_last = g_attach_last_ms.load();
  resp["attach_seconds"] =
      minijson::Value(attach_last >= 0 ? attach_last / 1000.0 : -1.0);
  // Current device op (warm-runner round-trip): age + declared budget.
  long long op_start = g_op_start_ms.load();
  resp["op_in_flight"] = minijson::Value(op_start > 0);
  resp["op_age_s"] =
      minijson::Value(op_start > 0 ? (now - op_start) / 1000.0 : 0.0);
  resp["op_timeout_s"] = minijson::Value(
      op_start > 0 ? g_op_timeout_ms.load() / 1000.0 : 0.0);
  long long last_ok = g_last_op_ok_ms.load();
  resp["last_device_op_age_s"] =
      minijson::Value(last_ok > 0 ? (now - last_ok) / 1000.0 : -1.0);
  long long line = g_runner_line_ms.load();
  resp["runner_heartbeat_age_s"] =
      minijson::Value(line > 0 ? (now - line) / 1000.0 : -1.0);
  long long runner_pid = g_runner_pid_stat.load();
  bool runner_alive = g_runner_ready_stat.load();
  if (runner_alive && runner_pid > 0) {
    // The ready mirror goes stale when the runner dies SILENTLY (OOM kill
    // between requests): nothing notices until the next execute finds the
    // corpse. Peek at the child without reaping it (WNOWAIT — kill_runner's
    // waitpid still collects the zombie), so the probe sees a dead-idle
    // runner instead of an eternally "healthy" host.
    siginfo_t info;
    info.si_pid = 0;
    if (waitid(P_PID, static_cast<id_t>(runner_pid), &info,
               WEXITED | WNOHANG | WNOWAIT) == 0 &&
        info.si_pid == static_cast<pid_t>(runner_pid)) {
      runner_alive = false;
    }
  }
  resp["runner_alive"] = minijson::Value(runner_alive);
  resp["runner_pid"] = minijson::Value(static_cast<double>(runner_pid));
  if (!g_state.lease_require_token) {
    // The held lease token: lets an operator (or the probe) see which
    // generation this server will honor without sending a claim. REDACTED
    // in strict mode — there, possession of the token IS the dispatch
    // credential, and this route is as reachable from inside the sandbox
    // as /execute (strict operators read the boot/refusal logs instead).
    std::lock_guard<std::mutex> llock(g_lease_mutex);
    resp["lease_token"] = minijson::Value(g_lease_token);
  }
  resp["rss_bytes"] = minijson::Value(
      static_cast<double>(rss_bytes_of(static_cast<long long>(getpid()))));
  resp["runner_rss_bytes"] = minijson::Value(
      static_cast<double>(runner_pid > 0 ? rss_bytes_of(runner_pid) : -1));
  conn.send_response(200, "application/json", minijson::Value(resp).dump());
}

void handle_readyz(const minihttp::Request&, minihttp::Conn& conn) {
  // Readiness: 503 until the sandbox can actually serve its purpose (warm
  // runner hot, or warm mode off). This is what k8s readinessProbe targets,
  // so "pod Ready" still means "TPU hot" without the server's *existence*
  // depending on TPU init (the r01 failure mode).
  bool ready = !g_state.warm_enabled || g_warm_state.load() == kWarmReady;
  conn.send_response(ready ? 200 : 503, "application/json",
                     warm_status_body().dump());
}

void handle_warmup(const minihttp::Request&, minihttp::Conn& conn) {
  conn.drain_body();
  start_warm_async();
  conn.send_response(200, "application/json", warm_status_body().dump());
}

// POST /reset — generation turnover: scrub the warm runner (stray children,
// env, workspace modules) and wipe workspace + runtime-packages, keeping the
// process and its TPU lease alive. 409 ⇒ not scrubbable (runner cold, mid-
// rewarm after a timeout kill, or reset failed); the control plane must then
// dispose the whole sandbox instead of reusing it. This is the mechanism that
// separates the chip lease from the disposable sandbox: single-use WORKSPACE,
// reusable DEVICE PROCESS (reference pods pay a full respawn here,
// kubernetes_code_executor.py:263-279 — a fresh pod per request).
void handle_reset(const minihttp::Request& req, minihttp::Conn& conn) {
  // A /reset from a fenced predecessor's control path (a retry racing a
  // dispose) must not wipe the successor's workspace mid-request.
  const double t_req = mono_s();
  if (reject_stale_lease(req, conn)) return;
  std::string traceparent = req.header("traceparent");
  conn.drain_body();
  std::lock_guard<std::mutex> lock(g_state.exec_mutex);
  auto refuse = [&conn](const char* reason) {
    minijson::Object resp;
    resp["ok"] = minijson::Value(false);
    resp["reason"] = minijson::Value(std::string(reason));
    conn.send_response(409, "application/json", minijson::Value(resp).dump());
  };
  double runner_sent = 0;
  minijson::Value runner_reply;
  if (g_state.warm_enabled && g_state.runner) {
    if (g_warm_state.load() != kWarmReady) {
      refuse("runner not warm");
      return;
    }
    std::lock_guard<std::mutex> rlock(g_state.runner_mutex);
    runner_sent = mono_s();
    if (!g_state.runner->alive() ||
        !g_state.runner->reset(8.0, runner_sent, runner_reply)) {
      {
        std::lock_guard<std::mutex> l(g_warm_transition_mutex);
        g_warm_state = kWarmFailed;
      }
      g_warm_cv.notify_all();
      refuse("runner reset failed");
      return;
    }
  }
  const double wipe_start = mono_s();
  // Runner scrubbed first (strays that could still write files are dead),
  // then the filesystem: workspace AND runtime-packages — a package the
  // previous user planted must never be importable by the next one. The
  // compilation-cache subtree is preserved EVERYWHERE: compiled XLA
  // kernels are the one cross-generation state turnover deliberately
  // keeps, and the historic layout put the cache dir under /tmp, squarely
  // inside the k8s backend's APP_RESET_EXTRA_WIPE_DIRS. Preservation is a
  // trust decision, not a no-op: entries CAN hold tenant-influenced bytes
  // (user code can write the dir; XLA constant-folding can bake input
  // data into artifacts), which is why the control plane only ever
  // harvests sandboxes that never ran tenant code — the preserved dir
  // stays pod-local state, never fleet state.
  // Gated on the kill switch: APP_COMPILE_CACHE=0 must restore EXACT
  // pre-cache reset behavior — a preserved-but-unserved cache dir would
  // keep the one cross-generation channel the switch exists to close.
  const std::string preserve =
      g_state.compile_cache_enabled ? g_state.compile_cache_dir
                                    : std::string();
  if (!wipe_dir_children(g_state.workspace, preserve) ||
      !wipe_dir_children(g_state.runtime_packages, preserve)) {
    refuse("workspace wipe incomplete");
    return;
  }
  for (const auto& dir : g_state.extra_wipe_dirs) {
    struct stat st;
    if (stat(dir.c_str(), &st) != 0) continue;  // absent dir leaks nothing
    if (!wipe_dir_children(dir, preserve)) {
      refuse("extra wipe dir incomplete");
      return;
    }
  }
  // The workspace is empty now: a stale manifest would let a conditional
  // upload from the NEXT generation 304 against content the wipe removed.
  {
    std::lock_guard<std::mutex> mlock(g_ws_manifest_mutex);
    g_ws_manifest.clear();
  }
  minijson::Value status = warm_status_body();
  status.as_object()["ok"] = minijson::Value(true);
  if (!traceparent.empty()) {
    // Same kind of block as /execute's: the turnover's two stages, the
    // runner's scrub inside the first, offsets from this request's arrival.
    double block_at = mono_s();
    minijson::Array trace_spans;
    if (runner_sent > 0) {
      add_trace_span(trace_spans, "runner_reset", runner_sent - t_req,
                     wipe_start - runner_sent);
      add_runner_stages(trace_spans, runner_reply.get("stages"),
                        runner_sent - t_req, "runner_reset");
    }
    add_trace_span(trace_spans, "wipe", wipe_start - t_req,
                   block_at - wipe_start);
    minijson::Object trace;
    trace["traceparent"] = minijson::Value(traceparent);
    trace["spans"] = minijson::Value(trace_spans);
    trace["total_s"] = minijson::Value(block_at - t_req);
    status.as_object()["trace"] = minijson::Value(trace);
  }
  conn.send_response(200, "application/json", status.dump());
}

// POST /snapshot and POST /restore — session durability: pass an
// interpreter-state op over the warm-runner pipe. The workspace BYTES never
// ride these routes (they ride the existing manifest-negotiated PUT/GET
// paths, so an unchanged workspace moves zero bytes); this is only the
// serialized interpreter state (env deltas, cwd, workspace-module globals).
// 409 ⇒ no warm runner to snapshot/restore (cold, mid-rewarm, or the op
// failed and killed it); the control plane treats that as "recreate fresh",
// never as a half-restored session.
void handle_snapshot_op(const minihttp::Request& req, minihttp::Conn& conn,
                        bool is_restore) {
  // Same fencing discipline as /reset: a fenced predecessor's control path
  // must not snapshot (or worse, restore into) the successor's runner.
  if (reject_stale_lease(req, conn)) return;
  std::string body = conn.read_body();
  minijson::Value parsed;
  if (!body.empty()) {
    try {
      parsed = minijson::parse(body);
    } catch (const std::exception&) {
      conn.send_response(400, "application/json", "{\"error\":\"bad json\"}");
      return;
    }
  }
  double timeout_s = parsed.get_number("timeout", 30.0);
  std::lock_guard<std::mutex> lock(g_state.exec_mutex);
  auto refuse = [&conn](const char* reason) {
    minijson::Object resp;
    resp["ok"] = minijson::Value(false);
    resp["reason"] = minijson::Value(std::string(reason));
    conn.send_response(409, "application/json", minijson::Value(resp).dump());
  };
  if (!g_state.warm_enabled || !g_state.runner) {
    refuse("no warm runner");
    return;
  }
  if (g_warm_state.load() != kWarmReady) {
    refuse("runner not warm");
    return;
  }
  minijson::Object op;
  if (is_restore) {
    op["op"] = minijson::Value(std::string("restore"));
    op["state"] = parsed.get("state");
  } else {
    op["op"] = minijson::Value(std::string("snapshot"));
    double max_bytes = parsed.get_number("max_bytes", 0.0);
    if (max_bytes > 0) op["max_bytes"] = minijson::Value(max_bytes);
  }
  std::lock_guard<std::mutex> rlock(g_state.runner_mutex);
  minijson::Value response;
  if (!g_state.runner->alive() ||
      g_state.runner->execute(minijson::Value(op).dump(), timeout_s,
                              response) != WarmRunner::ExecResult::kOk) {
    // The op killed the runner (timeout/death): same state machine as a
    // failed reset — this sandbox can no longer be trusted warm.
    {
      std::lock_guard<std::mutex> l(g_warm_transition_mutex);
      g_warm_state = kWarmFailed;
    }
    g_warm_cv.notify_all();
    refuse(is_restore ? "runner restore failed" : "runner snapshot failed");
    return;
  }
  conn.send_response(200, "application/json", response.dump());
}

void handle_snapshot(const minihttp::Request& req, minihttp::Conn& conn) {
  handle_snapshot_op(req, conn, /*is_restore=*/false);
}

void handle_restore(const minihttp::Request& req, minihttp::Conn& conn) {
  handle_snapshot_op(req, conn, /*is_restore=*/true);
}

void route(const minihttp::Request& req, minihttp::Conn& conn) {
  if (req.method == "POST" && req.target == "/execute") {
    handle_execute(req, conn);
  } else if (req.method == "POST" && req.target == "/execute-batch") {
    handle_execute_batch(req, conn);
  } else if (req.method == "POST" && req.target == "/execute/stream") {
    handle_execute_stream(req, conn);
  } else if (req.method == "POST" && req.target == "/warmup") {
    handle_warmup(req, conn);
  } else if (req.method == "POST" && req.target == "/reset") {
    handle_reset(req, conn);
  } else if (req.method == "POST" && req.target == "/snapshot") {
    handle_snapshot(req, conn);
  } else if (req.method == "POST" && req.target == "/restore") {
    handle_restore(req, conn);
  } else if (req.method == "POST" && req.target == "/lease") {
    handle_lease(req, conn);
  } else if (req.method == "GET" && req.target == "/workspace-manifest") {
    handle_manifest(req, conn);
  } else if (req.method == "GET" && req.target == "/compile-cache-manifest") {
    handle_cc_manifest(req, conn);
  } else if (req.method == "GET" && req.target == "/healthz") {
    handle_healthz(req, conn);
  } else if (req.method == "GET" && req.target == "/device-stats") {
    handle_device_stats(req, conn);
  } else if (req.method == "GET" && req.target == "/readyz") {
    handle_readyz(req, conn);
  } else if (req.method == "POST" &&
             req.target.rfind(std::string(kCopyRoute) + "/", 0) == 0) {
    handle_copy_from_storage(req, conn);
  } else if (req.method == "PUT") {
    handle_upload(req, conn);
  } else if (req.method == "GET" || req.method == "HEAD") {
    handle_download(req, conn);
  } else {
    conn.drain_body();
    conn.send_response(404, "application/json", "{\"error\":\"no route\"}");
  }
}

std::string self_dir() {
  char buf[PATH_MAX];
  ssize_t n = readlink("/proc/self/exe", buf, sizeof(buf) - 1);
  if (n <= 0) return ".";
  buf[n] = 0;
  std::string p(buf);
  size_t slash = p.rfind('/');
  return slash == std::string::npos ? "." : p.substr(0, slash);
}

}  // namespace

int main() {
  g_boot_ms = now_ms();
  std::string listen_addr = env_or("APP_LISTEN_ADDR", "0.0.0.0:8000");
  g_state.workspace = env_or("APP_WORKSPACE", "/workspace");
  g_state.runtime_packages = env_or("APP_RUNTIME_PACKAGES", "/runtime-packages");
  g_state.python = env_or("APP_PYTHON", "python3");
  std::string exe_dir = self_dir();
  auto sibling = [&exe_dir](const std::string& name) {
    std::string p = exe_dir + "/" + name;
    if (access(p.c_str(), R_OK) == 0) return p;
    return exe_dir + "/../" + name;  // binary lives in build/, scripts beside it
  };
  g_state.runner_script = env_or("APP_RUNNER_SCRIPT", sibling("runner.py"));
  g_state.deps_script = env_or("APP_DEPS_SCRIPT", sibling("deps.py"));
  g_state.launch_script = env_or("APP_LAUNCH_SCRIPT", sibling("launch.py"));
  g_state.warm_enabled = env_flag("APP_WARM_RUNNER", true);
  g_state.warm_eager = env_flag("APP_WARM_EAGER", true);
  g_state.runner_holds_device =
      g_state.warm_enabled && env_flag("APP_WARM_IMPORT_JAX", true);
  g_state.auto_install = env_flag("APP_AUTO_INSTALL_DEPS", false);
  g_state.manifest_enabled = env_flag("APP_WORKSPACE_MANIFEST", true);
  g_state.storage_dir = env_or("APP_STORAGE_OBJECTS_DIR", "");
  {
    // The fleet compile cache serves the same dir JAX writes its
    // persistent compilation cache to; no dir (or APP_COMPILE_CACHE=0)
    // removes the routes AND the reset-wipe exclusion.
    std::string cc = env_or("JAX_COMPILATION_CACHE_DIR", "");
    while (cc.size() > 1 && cc.back() == '/') cc.pop_back();
    g_state.compile_cache_dir = cc;
    g_state.compile_cache_enabled =
        !cc.empty() && env_flag("APP_COMPILE_CACHE", true);
    if (g_state.compile_cache_enabled) {
      // mkdir -p: the dir may be several levels deep (the default lives
      // under /var/tmp/<service>/) and must exist before the first seed
      // PUT or manifest GET lands.
      std::string partial;
      for (size_t i = 0; i <= cc.size(); ++i) {
        char c = i < cc.size() ? cc[i] : '/';
        if (c == '/' && !partial.empty()) mkdir(partial.c_str(), 0777);
        partial += c;
      }
    }
  }
  {
    std::string dirs = env_or("APP_RESET_EXTRA_WIPE_DIRS", "");
    std::string home = env_or("HOME", "");
    std::string cur;
    for (size_t i = 0; i <= dirs.size(); ++i) {
      char c = i < dirs.size() ? dirs[i] : ':';
      if (c == ':') {
        if (!cur.empty()) {
          if (cur[0] == '~' && !home.empty()) cur = home + cur.substr(1);
          g_state.extra_wipe_dirs.push_back(cur);
        }
        cur.clear();
      } else {
        cur += c;
      }
    }
  }
  g_state.num_hosts = static_cast<int>(env_num("APP_NUM_HOSTS", 1));
  // Local-subprocess backend sets this so a SIGKILLed control plane can't
  // orphan sandboxes. SIGTERM (not SIGKILL) so the shutdown handler below
  // still reaps the runner's session. Off in pods, where the server is the
  // container's PID 1 and GC is the ownerReference's job.
  if (env_flag("APP_PARENT_DEATH_EXIT", false)) {
    prctl(PR_SET_PDEATHSIG, SIGTERM);
  }
  g_state.default_timeout = env_num("APP_DEFAULT_TIMEOUT", 60.0);
  g_state.max_output = static_cast<size_t>(env_num("APP_MAX_OUTPUT_BYTES", 10485760));
  g_state.limit_caps = limits::caps_from_env();
  g_state.limit_poll_interval = env_num("APP_LIMIT_POLL_INTERVAL", 0.1);
  g_state.lease_require_token = env_flag("APP_LEASE_REQUIRE_TOKEN", false);
  if (g_state.lease_require_token)
    log_msg("strict lease mode: tokenless dispatches 409 once leased");
  // cgroup-v2 hard enforcement: detect a writable, memory+pids-delegated
  // v2 subtree (the one this process lives in, or APP_CGROUP_ROOT) and
  // park the warm runner group in a caps-bounded scope. Every failure
  // mode — v1/hybrid host, read-only cgroupfs, shared subtree, kill
  // switch — falls back cleanly to the rlimits+watchdog layers alone.
  g_cgroup = cgroup::init(env_flag("APP_CGROUP_ENFORCE", true));
  if (g_cgroup.enabled) {
    long long cap_mem = g_state.limit_caps.memory_bytes;
    long long cap_nproc = g_state.limit_caps.nproc;
    if (cap_mem > 0 || cap_nproc > 0) {
      // The runner scope bounds the SANDBOX for its whole life with the
      // boot caps (per-request tighten-only overrides stay the watchdog's
      // job). memory_bytes means "beyond the warm baseline", and a cgroup
      // counts from zero — the headroom absorbs the runner's own RSS
      // (jax + libtpu can be GiBs on real devices; tune per deployment).
      // The pids headroom covers the runner's interpreter/runtime threads
      // (the pids controller counts tasks, threads included).
      long long headroom = static_cast<long long>(
          env_num("APP_CGROUP_RUNNER_HEADROOM_BYTES", 2147483648.0));
      g_runner_scope = cgroup::Scope::create(
          g_cgroup, "runner", cap_mem > 0 ? cap_mem + headroom : 0,
          cap_nproc > 0 ? cap_nproc + 512 : 0);
      if (g_runner_scope.active())
        g_runner_cgroup_procs = g_runner_scope.procs_path();
    }
    log_msg("cgroup-v2 enforcement armed (base=%s runner_scope=%d)",
            g_cgroup.base.c_str(), (int)g_runner_scope.active());
  } else {
    log_msg("cgroup-v2 enforcement unavailable (%s); rlimits+watchdog only",
            g_cgroup.reason.c_str());
  }
  if (g_state.limit_caps.any()) {
    log_msg(
        "resource limits armed: mem=%lld cpu=%.0fs nproc=%lld nofile=%lld "
        "fsize=%lld disk=%lld (0 = off)",
        g_state.limit_caps.memory_bytes, g_state.limit_caps.cpu_seconds,
        g_state.limit_caps.nproc, g_state.limit_caps.nofile,
        g_state.limit_caps.fsize_bytes, g_state.limit_caps.disk_bytes);
  }

  mkdir(g_state.workspace.c_str(), 0777);
  mkdir(g_state.runtime_packages.c_str(), 0777);

  // Graceful shutdown (kubelet pod stop, local backend teardown): reap the
  // runner's whole session, then exit.
  struct sigaction sa {};
  sa.sa_handler = handle_shutdown_signal;
  sigaction(SIGTERM, &sa, nullptr);
  sigaction(SIGINT, &sa, nullptr);

  if (!g_state.warm_enabled && g_state.num_hosts > 1) {
    // A multi-host slice only exists through the warm runner's
    // jax.distributed mesh — refusing a misconfigured boot beats presenting
    // a sandbox whose user code silently sees no mesh.
    log_msg("APP_NUM_HOSTS>1 requires the warm runner; exiting");
    return 1;
  }
  double ready_timeout = env_num("APP_RUNNER_READY_TIMEOUT", 180.0);
  WarmRunner runner(g_state.python, g_state.runner_script, g_state.workspace,
                    ready_timeout);
  if (g_state.warm_enabled) g_state.runner = &runner;

  // Announce the port BEFORE any TPU init: "reachable" must not wait on
  // "hot". Warm-up runs on a background thread (eager mode) or when the
  // control plane POSTs /warmup after acquiring its per-chip lease.
  minihttp::Server server(listen_addr, route);
  printf("LISTENING port=%d\n", server.port());
  fflush(stdout);
  log_msg("executor-server listening on port %d (workspace=%s warm=%d eager=%d)",
          server.port(), g_state.workspace.c_str(), (int)g_state.warm_enabled,
          (int)g_state.warm_eager);
  if (g_state.warm_enabled && g_state.warm_eager) start_warm_async();
  server.serve_forever();
}
