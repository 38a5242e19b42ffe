// IPC fabric + monitor loopback tests. The reference forks a child playing
// the libkineto client over a real abstract UNIX socket
// (dynolog/tests/tracing/IPCMonitorTest.cpp:34-60); here the client is a
// second FabricManager endpoint in-process, which exercises the same kernel
// datagram path without fork()'s interference with test output.
#include "src/tracing/IPCMonitor.h"

#include <sys/socket.h>
#include <sys/un.h>
#include <dirent.h>
#include <sys/wait.h>
#include <unistd.h>

#include <poll.h>

#include <chrono>
#include <cstddef>
#include <cstring>
#include <thread>

#include "src/core/Histograms.h"
#include "src/core/SpanJournal.h"
#include "src/ipc/FabricManager.h"
#include "src/metrics/MetricStore.h"
#include "src/tests/minitest.h"

using namespace dynotpu;
using namespace dynotpu::tracing;

namespace {

std::string uniqueName(const char* prefix) {
  return std::string(prefix) + "_" + std::to_string(getpid());
}

// Client-side encoding of the "req" wire message: ClientRequest header +
// int32 pid array (the layout libkineto's IpcFabricConfigClient sends).
std::unique_ptr<ipc::Message> makeRequestMsg(
    int64_t jobId,
    const std::vector<int32_t>& pids,
    int32_t configType) {
  size_t size = sizeof(ClientRequest) + sizeof(int32_t) * pids.size();
  std::vector<unsigned char> buf(size);
  auto* req = reinterpret_cast<ClientRequest*>(buf.data());
  req->configType = configType;
  req->nPids = static_cast<int32_t>(pids.size());
  req->jobId = jobId;
  std::memcpy(
      buf.data() + sizeof(ClientRequest), pids.data(),
      sizeof(int32_t) * pids.size());
  return ipc::Message::create(buf.data(), size, kMsgTypeRequest);
}

// Waits for one datagram at `client`; returns the milliseconds it took,
// or -1 after `limitMs`. The blocking loop answers at its wake-up, so a
// reply that needs the loop's own poll timeout (250 ms) is a failure of
// the wake, which the callers' limits tell apart.
double recvWithinMs(ipc::FabricManager& client, int limitMs) {
  auto start = std::chrono::steady_clock::now();
  auto elapsedMs = [&start] {
    return std::chrono::duration<double, std::milli>(
               std::chrono::steady_clock::now() - start)
        .count();
  };
  while (!client.recv()) {
    double left = limitMs - elapsedMs();
    if (left <= 0) {
      return -1;
    }
    pollfd pfd{client.fd(), POLLIN, 0};
    ::poll(&pfd, 1, static_cast<int>(left) + 1);
  }
  return elapsedMs();
}

// A monitor served by its own thread, as the daemon runs it: loop()
// blocks in poll(2) between messages. Joined by stop().
struct ServedMonitor {
  explicit ServedMonitor(IPCMonitor& monitor)
      : monitor_(monitor), thread_([&monitor] { monitor.loop(); }) {}
  ~ServedMonitor() {
    if (thread_.joinable()) {
      stop(); // a failed ASSERT unwinds past the test's own stop()
    }
  }
  // Milliseconds stop() needed to get the blocked thread out and joined.
  double stop() {
    auto start = std::chrono::steady_clock::now();
    monitor_.stop();
    thread_.join();
    return std::chrono::duration<double, std::milli>(
               std::chrono::steady_clock::now() - start)
        .count();
  }
  IPCMonitor& monitor_;
  std::thread thread_;
};

} // namespace

TEST(IpcFabric, SendRecvRoundTrip) {
  auto nameA = uniqueName("dynotpu_test_a");
  auto nameB = uniqueName("dynotpu_test_b");
  auto a = ipc::FabricManager::factory(nameA);
  auto b = ipc::FabricManager::factory(nameB);
  ASSERT_TRUE(a && b);

  auto msg = ipc::Message::createFromString("hello fabric", "test");
  EXPECT_TRUE(a->sync_send(*msg, nameB));
  ASSERT_TRUE(b->poll_recv(100));
  auto received = b->retrieve_msg();
  ASSERT_TRUE(received != nullptr);
  EXPECT_EQ(received->payloadString(), std::string("hello fabric"));
  EXPECT_EQ(std::string(received->metadata.type), std::string("test"));
  EXPECT_EQ(received->src, nameA);

  // Reply using the src address.
  auto reply = ipc::Message::createFromString("pong", "test");
  EXPECT_TRUE(b->sync_send(*reply, received->src));
  ASSERT_TRUE(a->poll_recv(100));
  EXPECT_EQ(a->retrieve_msg()->payloadString(), std::string("pong"));
}

TEST(IpcFabric, ScmRightsFdPassing) {
  // SCM_RIGHTS across processes (reference Endpoint.h:235-261): the child
  // passes the read end of a pipe over the fabric socket; the parent's
  // kernel-installed duplicate reads what the child writes after sending —
  // proof the descriptor itself crossed, not just bytes.
  auto nameA = uniqueName("dynotpu_test_fd_a");
  auto nameB = uniqueName("dynotpu_test_fd_b");
  ipc::EndPoint receiver(nameB);

  int pipeFds[2];
  ASSERT_TRUE(::pipe(pipeFds) == 0);
  pid_t child = ::fork();
  ASSERT_TRUE(child >= 0);
  if (child == 0) {
    ipc::EndPoint sender(nameA);
    char tag = 'F';
    bool sent = false;
    for (int i = 0; i < 100 && !sent; ++i) {
      sent = sender.trySendFd(nameB, {{&tag, 1}}, pipeFds[0]);
      if (!sent) {
        ::usleep(10'000);
      }
    }
    // Write through the write end AFTER sending, then exit: the parent can
    // only see this through the transferred descriptor.
    const char* data = "via-scm-rights";
    (void)!::write(pipeFds[1], data, 14);
    ::close(pipeFds[1]);
    ::_exit(sent ? 0 : 1);
  }
  ::close(pipeFds[1]); // parent only uses the received duplicate
  ::close(pipeFds[0]);

  char tag = 0;
  int receivedFd = -1;
  ssize_t n = -1;
  for (int i = 0; i < 200 && n < 0; ++i) {
    n = receiver.tryRecvFd({{&tag, 1}}, nullptr, &receivedFd);
    if (n < 0) {
      ::usleep(10'000);
    }
  }
  int status = 0;
  ::waitpid(child, &status, 0);
  ASSERT_EQ(n, ssize_t(1));
  EXPECT_EQ(tag, 'F');
  ASSERT_TRUE(receivedFd >= 0);
  char buf[32] = {};
  ssize_t got = ::read(receivedFd, buf, sizeof(buf));
  EXPECT_EQ(got, ssize_t(14));
  EXPECT_EQ(std::string(buf, 14), std::string("via-scm-rights"));
  ::close(receivedFd);
  EXPECT_EQ(WEXITSTATUS(status), 0);

  // A receiver that doesn't ask for the fd must not leak the installed
  // duplicate: loopback-send an fd, recv with receivedFd=nullptr, and the
  // process's open-fd count must return to baseline.
  auto countFds = [] {
    int n = 0;
    DIR* d = ::opendir("/proc/self/fd");
    for (dirent* e; (e = ::readdir(d));) {
      n += e->d_name[0] != '.';
    }
    ::closedir(d);
    return n;
  };
  int p2[2];
  ASSERT_TRUE(::pipe(p2) == 0);
  int baseline = countFds() - 2; // minus the pipe we close below
  char t2 = 'G';
  ASSERT_TRUE(receiver.trySendFd(nameB, {{&t2, 1}}, p2[0]));
  ::close(p2[0]);
  ::close(p2[1]);
  ssize_t n2 = -1;
  for (int i = 0; i < 200 && n2 < 0; ++i) {
    n2 = receiver.tryRecvFd({{&t2, 1}}, nullptr, /*receivedFd=*/nullptr);
    if (n2 < 0) {
      ::usleep(10'000);
    }
  }
  ASSERT_EQ(n2, ssize_t(1));
  EXPECT_EQ(countFds(), baseline);
}

TEST(IpcFabric, SendToMissingPeerFails) {
  auto a = ipc::FabricManager::factory(uniqueName("dynotpu_test_c"));
  ASSERT_TRUE(a != nullptr);
  auto msg = ipc::Message::createFromString("x", "test");
  EXPECT_FALSE(a->sync_send(*msg, "dynotpu_no_such_endpoint", 2, 1000));
}

TEST(IpcMonitor, ContextRegistrationRoundTrip) {
  auto mgr = std::make_shared<TraceConfigManager>(
      std::chrono::seconds(60), "/nonexistent");
  auto daemonName = uniqueName("dynotpu_test_daemon1");
  IPCMonitor monitor(mgr, daemonName);
  ASSERT_TRUE(monitor.active());

  auto clientName = uniqueName("dynotpu_test_client1");
  auto client = ipc::FabricManager::factory(clientName);
  ASSERT_TRUE(client != nullptr);

  ClientContext ctxt{/*device=*/2, /*pid=*/12345, /*jobId=*/777};
  auto msg = ipc::Message::createFromPod(ctxt, kMsgTypeContext);
  ASSERT_TRUE(client->sync_send(*msg, daemonName));

  // Daemon processes the registration and acks with the instance count.
  ASSERT_TRUE(monitor.pollOnce());
  ASSERT_TRUE(client->poll_recv(100));
  auto ack = client->retrieve_msg();
  ASSERT_TRUE(ack != nullptr);
  ASSERT_EQ(ack->metadata.size, sizeof(int32_t));
  int32_t count;
  std::memcpy(&count, ack->buf.get(), sizeof(count));
  EXPECT_EQ(count, 1);
}

TEST(IpcMonitor, OnDemandConfigRoundTrip) {
  auto mgr = std::make_shared<TraceConfigManager>(
      std::chrono::seconds(60), "/nonexistent");
  auto daemonName = uniqueName("dynotpu_test_daemon2");
  IPCMonitor monitor(mgr, daemonName);
  ASSERT_TRUE(monitor.active());

  auto clientName = uniqueName("dynotpu_test_client2");
  auto client = ipc::FabricManager::factory(clientName);
  ASSERT_TRUE(client != nullptr);
  constexpr int32_t kActivities =
      static_cast<int32_t>(TraceConfigType::ACTIVITIES);

  // First poll: registers, empty config back.
  auto poll = makeRequestMsg(55, {4321}, kActivities);
  ASSERT_TRUE(client->sync_send(*poll, daemonName));
  ASSERT_TRUE(monitor.pollOnce());
  ASSERT_TRUE(client->poll_recv(100));
  EXPECT_EQ(client->retrieve_msg()->payloadString(), std::string(""));
  EXPECT_EQ(mgr->processCount(55), 1);

  // Operator pushes a config; next client poll receives it.
  mgr->setOnDemandConfig(55, {}, "ACTIVITIES_DURATION_MSECS=750", kActivities, 3);
  ASSERT_TRUE(client->sync_send(*poll, daemonName));
  ASSERT_TRUE(monitor.pollOnce());
  ASSERT_TRUE(client->poll_recv(100));
  EXPECT_EQ(
      client->retrieve_msg()->payloadString(),
      std::string("ACTIVITIES_DURATION_MSECS=750\n"));

  // The same exchange against the blocking loop on its own thread: the
  // request itself wakes it, with no tick to wait out. Let the thread
  // reach its poll first, so the exchange meets a BLOCKED thread.
  auto before = IPCMonitor::wakeCounts();
  ServedMonitor served(monitor);
  std::this_thread::sleep_for(std::chrono::milliseconds(20));
  mgr->setOnDemandConfig(55, {}, "ACTIVITIES_DURATION_MSECS=751", kActivities, 3);
  // The post alone wakes it (nobody subscribed, so nothing is sent);
  // wait for that wake, or the request's would count for both.
  for (int i = 0; i < 2000 && IPCMonitor::wakeCounts().posted == before.posted;
       ++i) {
    std::this_thread::sleep_for(std::chrono::milliseconds(1));
  }
  EXPECT_TRUE(IPCMonitor::wakeCounts().posted > before.posted);
  ASSERT_TRUE(client->sync_send(*poll, daemonName));
  double tookMs = recvWithinMs(*client, 2000);
  EXPECT_TRUE(tookMs >= 0 && tookMs < 200);
  EXPECT_EQ(
      client->retrieve_msg()->payloadString(),
      std::string("ACTIVITIES_DURATION_MSECS=751\n"));
  EXPECT_TRUE(IPCMonitor::wakeCounts().message > before.message);
  // stop() reaches a thread blocked in poll(2) through the wake
  // descriptor, not at the poll's 250 ms timeout.
  std::this_thread::sleep_for(std::chrono::milliseconds(20));
  EXPECT_TRUE(served.stop() < 200);
}

TEST(IpcMonitor, PerfStatsLandInMetricStore) {
  auto mgr = std::make_shared<TraceConfigManager>(
      std::chrono::seconds(60), "/nonexistent");
  auto store = std::make_shared<MetricStore>(1000, 64);
  auto daemonName = uniqueName("dynotpu_test_daemon3");
  IPCMonitor monitor(mgr, daemonName, store);
  ASSERT_TRUE(monitor.active());

  auto clientName = uniqueName("dynotpu_test_client3");
  auto client = ipc::FabricManager::factory(clientName);
  ASSERT_TRUE(client != nullptr);

  ClientPerfStats stats{};
  stats.pid = 4321;
  stats.jobId = 88;
  stats.windowS = 10.0;
  stats.steps = 2000;
  stats.stepTimeP50Ms = 4.5;
  stats.stepTimeP95Ms = 6.0;
  stats.stepTimeMaxMs = 21.0;

  // Unregistered job: dropped (any local process could otherwise mint
  // unbounded job<N>.* series or spoof another job's throughput).
  auto msg = ipc::Message::createFromPod(stats, kMsgTypePerfStats);
  ASSERT_TRUE(client->sync_send(*msg, daemonName));
  ASSERT_TRUE(monitor.pollOnce());
  EXPECT_EQ(store->latest().count("job88.steps_per_sec"), size_t(0));

  // Registered (a trace-config poll registers the process): accepted.
  mgr->obtainOnDemandConfig(
      88, {4321}, static_cast<int32_t>(TraceConfigType::ACTIVITIES));
  msg = ipc::Message::createFromPod(stats, kMsgTypePerfStats);
  ASSERT_TRUE(client->sync_send(*msg, daemonName));
  ASSERT_TRUE(monitor.pollOnce());

  auto latest = store->latest();
  ASSERT_TRUE(latest.count("job88.steps_per_sec") == 1);
  EXPECT_EQ(latest["job88.steps_per_sec"].first, 200.0);
  EXPECT_EQ(latest["job88.step_time_p50_ms"].first, 4.5);
  EXPECT_EQ(latest["job88.step_time_p95_ms"].first, 6.0);
  EXPECT_EQ(latest["job88.step_time_max_ms"].first, 21.0);

  // Idle window: rate goes to zero, stale percentiles are not re-written.
  stats.steps = 0;
  stats.stepTimeP50Ms = 0;
  stats.stepTimeP95Ms = 0;
  stats.stepTimeMaxMs = 0;
  msg = ipc::Message::createFromPod(stats, kMsgTypePerfStats);
  ASSERT_TRUE(client->sync_send(*msg, daemonName));
  ASSERT_TRUE(monitor.pollOnce());
  latest = store->latest();
  EXPECT_EQ(latest["job88.steps_per_sec"].first, 0.0);
  EXPECT_EQ(latest["job88.step_time_p50_ms"].first, 4.5);

  // Hostile values (negative window, NaN) are rejected wholesale.
  stats.windowS = -1.0;
  stats.steps = 100;
  msg = ipc::Message::createFromPod(stats, kMsgTypePerfStats);
  ASSERT_TRUE(client->sync_send(*msg, daemonName));
  ASSERT_TRUE(monitor.pollOnce());
  latest = store->latest();
  EXPECT_EQ(latest["job88.steps_per_sec"].first, 0.0); // unchanged

  stats.windowS = 10.0;
  stats.stepTimeP50Ms = std::nan("");
  msg = ipc::Message::createFromPod(stats, kMsgTypePerfStats);
  ASSERT_TRUE(client->sync_send(*msg, daemonName));
  ASSERT_TRUE(monitor.pollOnce());
  latest = store->latest();
  EXPECT_EQ(latest["job88.steps_per_sec"].first, 0.0); // unchanged
}

TEST(IpcMonitor, PerfStatsJobCapAndInfRate) {
  auto mgr = std::make_shared<TraceConfigManager>(
      std::chrono::seconds(60), "/nonexistent");
  auto store = std::make_shared<MetricStore>(1000, 2048);
  auto daemonName = uniqueName("dynotpu_test_daemon4");
  IPCMonitor monitor(mgr, daemonName, store);
  ASSERT_TRUE(monitor.active());
  auto client =
      ipc::FabricManager::factory(uniqueName("dynotpu_test_client4"));
  ASSERT_TRUE(client != nullptr);
  constexpr int32_t kActivities =
      static_cast<int32_t>(TraceConfigType::ACTIVITIES);

  // Individually-finite fields whose quotient overflows: rejected.
  ClientPerfStats inf{};
  inf.pid = 1;
  inf.jobId = 1;
  inf.windowS = 1e-308;
  inf.steps = 1e308;
  mgr->obtainOnDemandConfig(1, {1}, kActivities);
  auto msg = ipc::Message::createFromPod(inf, kMsgTypePerfStats);
  ASSERT_TRUE(client->sync_send(*msg, daemonName));
  ASSERT_TRUE(monitor.pollOnce());
  EXPECT_EQ(store->latest().count("job1.steps_per_sec"), size_t(0));

  // Registered-job telemetry is capped at 64 distinct jobs per daemon
  // lifetime (store series never expire): jobs past the cap are dropped.
  for (int64_t job = 1; job <= 70; ++job) {
    mgr->obtainOnDemandConfig(job, {static_cast<int32_t>(job)}, kActivities);
    ClientPerfStats stats{};
    stats.pid = static_cast<int32_t>(job);
    stats.jobId = job;
    stats.windowS = 10.0;
    stats.steps = 100;
    stats.stepTimeP50Ms = 1.0;
    stats.stepTimeP95Ms = 2.0;
    stats.stepTimeMaxMs = 3.0;
    msg = ipc::Message::createFromPod(stats, kMsgTypePerfStats);
    ASSERT_TRUE(client->sync_send(*msg, daemonName));
    ASSERT_TRUE(monitor.pollOnce());
  }
  size_t jobsWithRate = 0;
  for (const auto& [name, _] : store->latest()) {
    if (name.find("steps_per_sec") != std::string::npos) {
      jobsWithRate++;
    }
  }
  EXPECT_EQ(jobsWithRate, size_t(64));
  EXPECT_EQ(store->latest().count("job64.steps_per_sec"), size_t(1));
  EXPECT_EQ(store->latest().count("job65.steps_per_sec"), size_t(0));
}

TEST(IpcFabric, SurvivesHostileDatagrams) {
  // The daemon's socket is reachable by any local process; raw garbage
  // must be dropped without crashing and without poisoning later traffic
  // (FabricManager.h kMaxPayload + truncated-datagram guards).
  auto victimName = uniqueName("dynotpu_test_victim");
  auto victim = ipc::FabricManager::factory(victimName);
  ASSERT_TRUE(victim != nullptr);

  int attacker = ::socket(AF_UNIX, SOCK_DGRAM, 0);
  ASSERT_TRUE(attacker >= 0);
  sockaddr_un dst{};
  dst.sun_family = AF_UNIX;
  dst.sun_path[0] = '\0'; // abstract namespace
  std::memcpy(dst.sun_path + 1, victimName.data(), victimName.size());
  // EndPoint::setAddress binds '\0' + name + '\0' — the trailing NUL is
  // part of the abstract address, so it must be counted here too or the
  // datagrams go to a different (nonexistent) name.
  socklen_t dstLen = static_cast<socklen_t>(
      offsetof(sockaddr_un, sun_path) + 1 + victimName.size() + 1);

  // (a) datagram shorter than the metadata header
  const char tiny[3] = {'x', 'y', 'z'};
  ASSERT_EQ(
      ::sendto(attacker, tiny, sizeof(tiny), 0,
               reinterpret_cast<sockaddr*>(&dst), dstLen),
      (ssize_t)sizeof(tiny));
  // (b) header claiming an absurd payload size
  ipc::Metadata huge;
  huge.size = ~0ULL;
  ASSERT_EQ(
      ::sendto(attacker, &huge, sizeof(huge), 0,
               reinterpret_cast<sockaddr*>(&dst), dstLen),
      (ssize_t)sizeof(huge));
  // (c) header claiming more payload than the datagram carries
  struct {
    ipc::Metadata md;
    char body[4] = {'a', 'b', 'c', 'd'};
  } lying;
  lying.md.size = 1000;
  ASSERT_EQ(
      ::sendto(attacker, &lying, sizeof(lying), 0,
               reinterpret_cast<sockaddr*>(&dst), dstLen),
      (ssize_t)sizeof(lying));
  ::close(attacker);

  // All three are consumed and dropped...
  for (int i = 0; i < 3; ++i) {
    victim->poll_recv(50);
  }
  EXPECT_TRUE(victim->retrieve_msg() == nullptr);

  // ...and a well-formed message still round-trips afterwards.
  auto sender = ipc::FabricManager::factory(uniqueName("dynotpu_test_atk2"));
  ASSERT_TRUE(sender != nullptr);
  auto msg = ipc::Message::createFromString("still alive", "test");
  EXPECT_TRUE(sender->sync_send(*msg, victimName));
  ASSERT_TRUE(victim->poll_recv(200));
  auto received = victim->retrieve_msg();
  ASSERT_TRUE(received != nullptr);
  EXPECT_EQ(received->payloadString(), std::string("still alive"));
}

MINITEST_MAIN()

TEST(IpcMonitor, KickSubscriberNotifiedOnConfigPost) {
  auto mgr = std::make_shared<TraceConfigManager>(
      std::chrono::seconds(60), "/nonexistent");
  auto daemonName = uniqueName("dynotpu_test_daemon_kick");
  IPCMonitor monitor(mgr, daemonName);
  ASSERT_TRUE(monitor.active());
  constexpr int32_t kActivities =
      static_cast<int32_t>(TraceConfigType::ACTIVITIES);

  auto clientName = uniqueName("dynotpu_test_kick_client");
  auto client = ipc::FabricManager::factory(clientName);
  ASSERT_TRUE(client != nullptr);

  // Register, then subscribe (the order the shim uses).
  auto poll = makeRequestMsg(88, {999}, kActivities);
  ASSERT_TRUE(client->sync_send(*poll, daemonName));
  ASSERT_TRUE(monitor.pollOnce());
  ASSERT_TRUE(client->poll_recv(100));
  client->retrieve_msg(); // empty config reply

  ClientSubscribe sub{/*pid=*/999, /*reserved=*/0, /*jobId=*/88};
  auto subMsg = ipc::Message::createFromPod(sub, kMsgTypeSubscribe);
  ASSERT_TRUE(client->sync_send(*subMsg, daemonName));
  ASSERT_TRUE(monitor.pollOnce());

  // No config posted yet: no kick.
  monitor.sendPendingKicks();
  EXPECT_FALSE(client->poll_recv(50));

  // Posting a config kicks the subscriber with the job id.
  mgr->setOnDemandConfig(88, {}, "ACTIVITIES_DURATION_MSECS=10", kActivities, 3);
  monitor.sendPendingKicks();
  ASSERT_TRUE(client->poll_recv(200));
  auto kick = client->retrieve_msg();
  ASSERT_TRUE(kick != nullptr);
  EXPECT_EQ(std::string(kick->metadata.type), std::string("kick"));
  ASSERT_EQ(kick->metadata.size, sizeof(int64_t));
  int64_t jobId = 0;
  std::memcpy(&jobId, kick->buf.get(), sizeof(jobId));
  EXPECT_EQ(jobId, 88);

  // Drained: a second sweep sends nothing.
  monitor.sendPendingKicks();
  EXPECT_FALSE(client->poll_recv(50));

  // A subscribe for an unregistered job is refused (hygiene gate).
  ClientSubscribe bad{/*pid=*/1, /*reserved=*/0, /*jobId=*/1234};
  auto badMsg = ipc::Message::createFromPod(bad, kMsgTypeSubscribe);
  ASSERT_TRUE(client->sync_send(*badMsg, daemonName));
  ASSERT_TRUE(monitor.pollOnce());
  mgr->setOnDemandConfig(1234, {}, "X=1", kActivities, 3);
  monitor.sendPendingKicks();
  EXPECT_FALSE(client->poll_recv(50));

  // Nonzero reserved fails closed.
  ClientSubscribe badRes{/*pid=*/999, /*reserved=*/7, /*jobId=*/88};
  auto badResMsg = ipc::Message::createFromPod(badRes, kMsgTypeSubscribe);
  ASSERT_TRUE(client->sync_send(*badResMsg, daemonName));
  ASSERT_TRUE(monitor.pollOnce());

  // The wake descriptor: readable from a post until the drain, and the
  // blocking loop, parked in poll(2) on it, kicks at once.
  pollfd posted{mgr->postedFd(), POLLIN, 0};
  ASSERT_TRUE(posted.fd >= 0);
  monitor.sendPendingKicks(); // drains what the refused post left
  EXPECT_EQ(::poll(&posted, 1, 0), 0);
  // Take the first config, so that the next post is not "busy".
  ASSERT_TRUE(client->sync_send(*poll, daemonName));
  ASSERT_TRUE(monitor.pollOnce());
  ASSERT_TRUE(client->poll_recv(100));
  client->retrieve_msg();
  auto before = IPCMonitor::wakeCounts();
  ServedMonitor served(monitor);
  std::this_thread::sleep_for(std::chrono::milliseconds(20));
  mgr->setOnDemandConfig(88, {}, "ACTIVITIES_DURATION_MSECS=11", kActivities, 3);
  double tookMs = recvWithinMs(*client, 2000);
  EXPECT_TRUE(tookMs >= 0 && tookMs < 200);
  auto blockingKick = client->retrieve_msg();
  ASSERT_TRUE(blockingKick != nullptr);
  EXPECT_EQ(std::string(blockingKick->metadata.type), std::string("kick"));
  EXPECT_TRUE(IPCMonitor::wakeCounts().posted > before.posted);
  // An idle blocked loop leaves by its timeout only: nothing is due, so
  // a 600 ms wait sees two or three timeouts and no other cause.
  before = IPCMonitor::wakeCounts();
  std::this_thread::sleep_for(std::chrono::milliseconds(600));
  auto idle = IPCMonitor::wakeCounts();
  EXPECT_EQ(idle.message, before.message);
  EXPECT_EQ(idle.posted, before.posted);
  EXPECT_TRUE(idle.timeout - before.timeout >= 1);
  EXPECT_TRUE(idle.timeout - before.timeout <= 4);
  EXPECT_TRUE(served.stop() < 200);
  // stop() leaves the descriptor set; the next drain clears it.
  monitor.sendPendingKicks();
  EXPECT_EQ(::poll(&posted, 1, 0), 0);
}

TEST(IpcMonitor, PerfStatsNonzeroReservedRejected) {
  // The wire doc pins ClientPerfStats.reserved as "must be 0 on the wire"
  // (IPCMonitor.h); the receive path must fail closed on a violation so
  // the field stays honestly reusable as a future version/flags word.
  auto mgr = std::make_shared<TraceConfigManager>(
      std::chrono::seconds(60), "/nonexistent");
  auto store = std::make_shared<MetricStore>(1000, 64);
  auto daemonName = uniqueName("dynotpu_test_daemon_res");
  IPCMonitor monitor(mgr, daemonName, store);
  ASSERT_TRUE(monitor.active());
  auto client = ipc::FabricManager::factory(uniqueName("dynotpu_test_cl_res"));
  ASSERT_TRUE(client != nullptr);

  // Register the job so rejection below can only come from `reserved`.
  mgr->obtainOnDemandConfig(
      99, {777}, static_cast<int32_t>(TraceConfigType::ACTIVITIES));

  ClientPerfStats stats{};
  stats.pid = 777;
  stats.reserved = 1;
  stats.jobId = 99;
  stats.windowS = 5.0;
  stats.steps = 50;
  auto msg = ipc::Message::createFromPod(stats, kMsgTypePerfStats);
  ASSERT_TRUE(client->sync_send(*msg, daemonName));
  ASSERT_TRUE(monitor.pollOnce());
  EXPECT_EQ(store->latest().count("job99.steps_per_sec"), size_t(0));

  // The identical payload with reserved cleared is accepted: the
  // rejection above keyed on the reserved word alone.
  stats.reserved = 0;
  msg = ipc::Message::createFromPod(stats, kMsgTypePerfStats);
  ASSERT_TRUE(client->sync_send(*msg, daemonName));
  ASSERT_TRUE(monitor.pollOnce());
  EXPECT_EQ(store->latest().count("job99.steps_per_sec"), size_t(1));
}

TEST(IpcMonitor, SpanDatagramsMergeIntoJournalAndHistogram) {
  // Python clients flush completed spans over the "span" datagram; the
  // monitor journals them (selftrace's merge) and folds trace.convert
  // durations into the scrape histogram. Reserved violations and
  // negative durations fail closed like every other handler.
  auto mgr = std::make_shared<TraceConfigManager>(
      std::chrono::seconds(60), "/nonexistent");
  auto daemonName = uniqueName("dynotpu_test_daemon_span");
  IPCMonitor monitor(mgr, daemonName, nullptr);
  ASSERT_TRUE(monitor.active());
  auto client = ipc::FabricManager::factory(uniqueName("dynotpu_test_cl_sp"));
  ASSERT_TRUE(client != nullptr);

  const uint64_t traceId = mintId(); // unique: the journal is process-wide
  ClientSpan span{};
  span.traceId = traceId;
  span.spanId = 0x200;
  span.parentId = 0x100;
  span.startUs = 1700000000000000;
  span.durUs = 2500;
  span.pid = 4321;
  std::strncpy(span.name, "trace.convert", sizeof(span.name) - 1);

  // Nonzero reserved: rejected, never journaled.
  span.reserved = 7;
  auto msg = ipc::Message::createFromPod(span, kMsgTypeSpan);
  ASSERT_TRUE(client->sync_send(*msg, daemonName));
  ASSERT_TRUE(monitor.pollOnce());
  for (const auto& s : SpanJournal::instance().snapshot()) {
    EXPECT_TRUE(s.traceId != traceId);
  }

  // Clean span: journaled with the client's identity intact.
  span.reserved = 0;
  msg = ipc::Message::createFromPod(span, kMsgTypeSpan);
  ASSERT_TRUE(client->sync_send(*msg, daemonName));
  ASSERT_TRUE(monitor.pollOnce());
  bool found = false;
  for (const auto& s : SpanJournal::instance().snapshot()) {
    if (s.traceId == traceId) {
      found = true;
      EXPECT_EQ(std::string(s.name), std::string("trace.convert"));
      EXPECT_EQ(s.parentId, uint64_t(0x100));
      EXPECT_EQ(s.pid, int32_t(4321));
      EXPECT_EQ(s.durUs, int64_t(2500));
    }
  }
  EXPECT_TRUE(found);
  // The convert duration reached the scrape histogram.
  std::string doc = HistogramRegistry::instance().renderOpenMetrics();
  EXPECT_TRUE(
      doc.find("dynolog_trace_convert_seconds_count 1") != std::string::npos);

  // Negative duration: rejected.
  span.durUs = -1;
  span.spanId = 0x300;
  msg = ipc::Message::createFromPod(span, kMsgTypeSpan);
  ASSERT_TRUE(client->sync_send(*msg, daemonName));
  ASSERT_TRUE(monitor.pollOnce());
  for (const auto& s : SpanJournal::instance().snapshot()) {
    EXPECT_TRUE(s.spanId != uint64_t(0x300));
  }
}
