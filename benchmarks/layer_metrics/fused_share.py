"""commit backend: create batches that reached the chip in a fused group launch, of all that reached it ([stats] shadow.* where the chip follows, else commit.group.*) (%)."""
from benchmarks.harness import readers

read = readers.fused_share
