// tpu-acx host-plane benchmark: enqueued ping-pong latency + partitioned
// bandwidth (two metrics the reference never published).
//
// Run under `acxrun -np 2 build/bench_pingpong [msg_bytes]`.
// Rank 0 prints one parseable line:
//   BENCH pingpong_p50_us=<v> pingpong_p99_us=<v> part_bw_gbps=<v> iters=<n>
//
// Ping-pong: rank 0 enqueues isend+irecv on the host queue and host-waits
// (the reference ring.c flow, full proxy + wire round trip); one-way
// latency = rtt/2. Partitioned: 64MiB in 16 partitions, Pready-marked
// out of order, timed over full rounds.

#include <algorithm>
#include <chrono>
#include <cstdio>
#include <cstdlib>
#include <vector>

#include <mpi.h>
#include <mpi-acx.h>

using Clock = std::chrono::steady_clock;

static double us_since(Clock::time_point t0) {
  return std::chrono::duration<double, std::micro>(Clock::now() - t0).count();
}

int main(int argc, char** argv) {
  int provided, rank, size;
  MPI_Init_thread(&argc, &argv, MPI_THREAD_MULTIPLE, &provided);
  MPI_Comm_rank(MPI_COMM_WORLD, &rank);
  MPI_Comm_size(MPI_COMM_WORLD, &size);
  if (size != 2) {
    if (rank == 0) std::fprintf(stderr, "bench_pingpong needs -np 2\n");
    MPI_Abort(MPI_COMM_WORLD, 2);
  }
  if (MPIX_Init()) MPI_Abort(MPI_COMM_WORLD, 2);

  const int peer = 1 - rank;
  const size_t msg = argc > 1 ? std::atol(argv[1]) : 8;
  const int warmup = 200, iters = 2000;
  std::vector<char> sbuf(msg, 1), rbuf(msg, 0);
  std::vector<double> lat;
  lat.reserve(iters);

  for (int it = -warmup; it < iters; it++) {
    auto t0 = Clock::now();
    MPIX_Request req[2];
    cudaStream_t s0 = 0;
    if (rank == 0) {
      MPIX_Isend_enqueue(sbuf.data(), (int)msg, MPI_BYTE, peer, 1,
                         MPI_COMM_WORLD, &req[0], MPIX_QUEUE_XLA_STREAM,
                         &s0);
      MPIX_Irecv_enqueue(rbuf.data(), (int)msg, MPI_BYTE, peer, 1,
                         MPI_COMM_WORLD, &req[1], MPIX_QUEUE_XLA_STREAM,
                         &s0);
    } else {
      MPIX_Irecv_enqueue(rbuf.data(), (int)msg, MPI_BYTE, peer, 1,
                         MPI_COMM_WORLD, &req[1], MPIX_QUEUE_XLA_STREAM,
                         &s0);
      MPIX_Isend_enqueue(sbuf.data(), (int)msg, MPI_BYTE, peer, 1,
                         MPI_COMM_WORLD, &req[0], MPIX_QUEUE_XLA_STREAM,
                         &s0);
    }
    MPIX_Wait(&req[1], MPI_STATUS_IGNORE);
    MPIX_Wait(&req[0], MPI_STATUS_IGNORE);
    if (it >= 0 && rank == 0) lat.push_back(us_since(t0) / 2.0);
  }

  // Partitioned bandwidth: 64 MiB, 16 partitions, 20 rounds.
  const int parts = 16;
  const size_t total = 64u << 20;
  std::vector<char> pbuf(total, 3);
  MPIX_Request preq;
  double gbps = 0;
  {
    if (rank == 0)
      MPIX_Psend_init(pbuf.data(), parts, (MPI_Count)(total / parts),
                      MPI_BYTE, peer, 7, MPI_COMM_WORLD, MPI_INFO_NULL,
                      &preq);
    else
      MPIX_Precv_init(pbuf.data(), parts, (MPI_Count)(total / parts),
                      MPI_BYTE, peer, 7, MPI_COMM_WORLD, MPI_INFO_NULL,
                      &preq);
    // Best of 3 sets x 20 rounds: the first set absorbs cold page faults
    // on the shm rings and destination buffer; report steady-state BW.
    const int rounds = 20, sets = 3;
    for (int set = 0; set < sets; set++) {
      MPI_Barrier(MPI_COMM_WORLD);
      auto t0 = Clock::now();
      for (int r = 0; r < rounds; r++) {
        MPIX_Start(&preq);
        if (rank == 0) {
          for (int p = parts - 1; p >= 0; p--) MPIX_Pready(p, &preq);
        }
        MPIX_Wait(&preq, MPI_STATUS_IGNORE);
      }
      MPI_Barrier(MPI_COMM_WORLD);
      double secs = us_since(t0) / 1e6;
      gbps = std::max(gbps, (double)total * rounds / secs / 1e9);
    }
    MPIX_Request_free(&preq);
  }

  if (rank == 0) {
    std::sort(lat.begin(), lat.end());
    std::printf("BENCH pingpong_p50_us=%.3f pingpong_p99_us=%.3f "
                "part_bw_gbps=%.3f iters=%d msg_bytes=%zu\n",
                lat[lat.size() / 2], lat[(size_t)(lat.size() * 0.99)], gbps,
                iters, msg);
  }

  // Striped-bandwidth sweep (ACX_BENCH_STRIPE_SWEEP=1, DESIGN.md §15):
  // one-way windowed stream per message size, receiver preposted so every
  // striped message takes the direct zero-copy delivery path. ACX_STRIPES
  // is fixed at transport construction, so one process measures ONE lane
  // count; a caller sweeps lane counts across runs and pairs the rows.
  // Run with ACX_RV_THRESHOLD=0 so large messages take the
  // eager (striping) path rather than rendezvous.
  if (getenv("ACX_BENCH_STRIPE_SWEEP") != nullptr) {
    const char* stripes_s = getenv("ACX_STRIPES");
    const size_t sizes[] = {256u << 10, 1u << 20, 4u << 20};
    for (size_t mb : sizes) {
      const int win = 16;                       // messages in flight
      const int rounds = (int)((96u << 20) / (mb * win)) + 1;
      std::vector<char> sb(mb, 5), rb(mb, 0);
      double best = 0;
      for (int set = 0; set < 3; set++) {       // best-of-3, cold set absorbed
        MPI_Barrier(MPI_COMM_WORLD);
        auto t0 = Clock::now();
        for (int r = 0; r < rounds; r++) {
          MPIX_Request req[16];
          cudaStream_t s0 = 0;
          for (int w = 0; w < win; w++) {
            if (rank == 0)
              MPIX_Isend_enqueue(sb.data(), (int)mb, MPI_BYTE, peer, 20 + w,
                                 MPI_COMM_WORLD, &req[w],
                                 MPIX_QUEUE_XLA_STREAM, &s0);
            else
              MPIX_Irecv_enqueue(rb.data(), (int)mb, MPI_BYTE, peer, 20 + w,
                                 MPI_COMM_WORLD, &req[w],
                                 MPIX_QUEUE_XLA_STREAM, &s0);
          }
          for (int w = 0; w < win; w++)
            MPIX_Wait(&req[w], MPI_STATUS_IGNORE);
        }
        MPI_Barrier(MPI_COMM_WORLD);
        const double secs = us_since(t0) / 1e6;
        const double bw = (double)mb * win * rounds / secs / 1e9;
        best = std::max(best, bw);
      }
      if (rank == 0)
        std::printf("BENCH_STRIPE stripes=%s msg_bytes=%zu bw_gbps=%.3f\n",
                    stripes_s != nullptr ? stripes_s : "1", mb, best);
    }
  }

  MPIX_Finalize();
  MPI_Finalize();
  return 0;
}
