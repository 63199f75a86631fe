/* A documented end-to-end walkthrough of the DROP-IN C++ surface
 * (include/mlsl.hpp): the program below is written exactly as a user of the
 * original library would write it (cf. the reference's
 * tests/examples/mlsl_example/mlsl_example.cpp) — create the environment, lay
 * out a data x model grid, register a two-operation graph, and run training
 * phases with asynchronous gradient synchronization. The only addition is the
 * MLSL::RunRanks launcher, which stands in for mpiexec: each MPI rank becomes
 * a rank thread over the shared TPU mesh (docs/MIGRATION.md).
 *
 * Build & run on the 8-device CPU mesh (the Makefile computes the portable
 * embed-Python link flags via python3-config):
 *   make -C native compat_example
 *   PYTHONPATH=. JAX_PLATFORMS=cpu \
 *       XLA_FLAGS=--xla_force_host_platform_device_count=8 \
 *       ./native/compat_example
 */

#include <cstdio>
#include <vector>

#include "mlsl.hpp"

using namespace MLSL;

namespace {

constexpr size_t kMinibatch = 8;  /* global; split over the data group */
constexpr size_t kFmIn = 16, kFmOut = 8, kFmSize = 4;

int rank_main(int argc, char** argv) {
  /* 1. Bootstrap (identical to the reference's Environment::GetEnv().Init) */
  Environment& env = Environment::GetEnv();
  env.Init(&argc, &argv);
  size_t world = env.GetProcessCount();
  size_t rank = env.GetProcessIdx();

  /* 2. Parallelism layout: data x model process grid */
  size_t model_parts = world >= 4 ? 2 : 1;
  Distribution* dist = env.CreateDistribution(world / model_parts, model_parts);

  /* 3. Register the graph: two fully-connected operations wired by an edge.
   * The library derives, per edge and parameter set, WHICH collective to run
   * on WHICH process subgroup. */
  Session* session = env.CreateSession();
  session->SetGlobalMinibatchSize(kMinibatch);

  OperationRegInfo* reg1 = session->CreateOperationRegInfo(OT_CC);
  reg1->AddInput(kFmIn, kFmSize, DT_FLOAT);
  reg1->AddOutput(kFmOut, kFmSize, DT_FLOAT);
  reg1->AddParameterSet(kFmIn * kFmOut, 1, DT_FLOAT, /*distributedUpdate=*/false,
                        CT_NONE);
  Operation* op1 = session->GetOperation(session->AddOperation(reg1, dist));
  session->DeleteOperationRegInfo(reg1);

  OperationRegInfo* reg2 = session->CreateOperationRegInfo(OT_CC);
  reg2->AddInput(kFmOut, kFmSize, DT_FLOAT);
  reg2->AddOutput(kFmIn, kFmSize, DT_FLOAT);
  reg2->AddParameterSet(kFmOut * kFmIn, 1, DT_FLOAT, /*distributedUpdate=*/true,
                        CT_NONE);
  Operation* op2 = session->GetOperation(session->AddOperation(reg2, dist));
  session->DeleteOperationRegInfo(reg2);

  op1->SetNext(op2, 0, 0);  /* op1's output 0 feeds op2's input 0 */
  session->Commit();        /* builds and compiles every per-edge collective */

  /* 4. Broadcast initial parameters from rank 0 (as the reference example
   * initializes weights identically on every rank) */
  ParameterSet* ps1 = op1->GetParameterSet(0);
  size_t n1 = ps1->GetLocalKernelCount() * ps1->GetKernelSize();
  std::vector<float> weights(n1, rank == 0 ? 0.5f : 0.0f);
  env.Wait(dist->Bcast(weights.data(), n1, DT_FLOAT, 0, GT_GLOBAL));

  /* 5. Training phases (the reference loop: Forward / Backward / Update).
   * StartGradientComm is ASYNC — the collective runs while this rank keeps
   * computing; WaitGradientComm delivers the reduced gradients. */
  for (int iter = 0; iter < 2; iter++) {
    std::vector<float> grads1(n1);
    for (size_t i = 0; i < n1; i++) grads1[i] = (float)(rank + 1);

    ps1->StartGradientComm(grads1.data());
    /* ... overlap: compute the next layer's gradients here ... */
    ParameterSet* ps2 = op2->GetParameterSet(0);
    size_t n2 = ps2->GetLocalKernelCount() * ps2->GetKernelSize();
    std::vector<float> grads2(n2);
    for (size_t i = 0; i < n2; i++) grads2[i] = (float)(rank + 1) * 0.5f;
    ps2->StartGradientComm(grads2.data());

    /* WaitGradientComm returns a pointer to the reduced gradients (the
     * library's wire buffer, reference semantics) */
    float* r1 = (float*)ps1->WaitGradientComm();
    /* op2 uses distributedUpdate (ZeRO-1): each data rank receives only its
     * OWNED shard of the reduced gradient; increments would be all-gathered
     * back by StartIncrementComm after the local optimizer step */
    float* r2 = (float*)ps2->WaitGradientComm();
    (void)r1;
    (void)r2;
  }

  /* 6. Statistics: per-op bytes/time accounting (enable with MLSL_STATS=1) */
  Statistics* stats = session->GetStats();
  if (stats->IsEnabled()) stats->Print();

  env.DeleteSession(session);
  env.DeleteDistribution(dist);
  env.Finalize();
  if (rank == 0) std::printf("compat example OK (world=%zu)\n", world);
  return 0;
}

}  // namespace

int main(int argc, char** argv) { return RunRanks(argc, argv, rank_main); }
