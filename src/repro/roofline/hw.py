"""TPU v5e hardware constants (the TARGET platform; container is CPU)."""

PEAK_FLOPS_BF16 = 197e12      # per chip, bf16
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link (intra-pod)
DCN_BW = 25e9                 # bytes/s per pod-crossing link (assumed)
HBM_BYTES = 16 * 2**30        # 16 GiB per chip
VMEM_BYTES = 128 * 2**20      # ~128 MiB VMEM per chip
SCOPED_VMEM_BYTES = 16 * 2**20  # Mosaic's default per-kernel VMEM limit
MXU_DIM = 128                 # systolic array tile
LANE = 128                    # vector lane width
SUBLANE = 8
