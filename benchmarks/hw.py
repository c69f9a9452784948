"""Hardware constants for the roofline + energy models.

TPU v5e numbers are the assignment's target constants. A100 / FlightLLM /
ReRAM-PIM constants parameterize the paper-§IV end-to-end comparison
methodology (energy per byte moved / per MAC, peak throughput, power).
Energy-per-bit figures follow the usual architecture-literature values
(HBM2e ~ 3.5-7 pJ/bit, DDR4 ~ 15-20 pJ/bit, on-chip SRAM ~ 0.1-0.2 pJ/bit);
compute energy from peak-power / peak-throughput.
"""
import dataclasses


@dataclasses.dataclass(frozen=True)
class Device:
    name: str
    peak_flops: float        # FLOP/s (bf16/fp16 dense)
    hbm_bw: float            # bytes/s
    mem_pj_per_byte: float   # off-chip access energy
    mac_pj: float            # energy per MAC (2 FLOPs)
    power_w: float           # board power (throughput/W comparisons)
    # static/leakage board power burned regardless of slot occupancy —
    # what a serving step pays for its IDLE rows (charged against the
    # recorded slot utilization in bench_e2e_energy). Rough
    # ~30% -of-board figures for the accelerators (clock gating leaves
    # leakage + HBM refresh + interconnect idle), lower for the FPGA/CIM
    # parts whose static share is small by construction.
    idle_w: float = 0.0


TPU_V5E = Device(
    name="tpu-v5e",
    peak_flops=197e12,
    hbm_bw=819e9,
    mem_pj_per_byte=30.0,     # ~3.75 pJ/bit HBM2e class
    mac_pj=0.56,              # ~220W core budget / 197 TFLOP/s (2 FLOP/MAC)
    power_w=220.0,
    idle_w=66.0,
)

ICI_BW = 50e9        # bytes/s per link, v5e
DCN_BW = 6.25e9      # bytes/s per host, cross-pod (50 Gbit)

A100 = Device(
    name="a100-80g",
    peak_flops=312e12,        # fp16 tensor core (dense)
    hbm_bw=2.0e12,
    mem_pj_per_byte=35.0,
    mac_pj=1.3,               # ~400W / 312 TFLOP/s
    power_w=400.0,
    idle_w=110.0,
)

FLIGHTLLM = Device(
    name="flightllm-u280",
    peak_flops=1.5e12,        # sparse-aware FPGA engine, effective
    hbm_bw=460e9,
    mem_pj_per_byte=35.0,
    mac_pj=2.0,
    power_w=45.0,
    idle_w=8.0,
)

# The paper's ReRAM/DCIM design: weights stationary in CIM macros (near-zero
# weight movement), 89 TOPS/W-class digital CIM macro [ISSCC'21 ref 40 in
# the paper] => ~0.011 pJ/MAC core; KV/activation movement dominates.
PIM = Device(
    name="reram-pim",
    peak_flops=20e12,
    hbm_bw=100e9,             # off-chip only for spilled KV cache
    mem_pj_per_byte=30.0,
    mac_pj=0.022,             # 89 TOPS/W digital CIM
    power_w=25.0,
    idle_w=3.0,
)
