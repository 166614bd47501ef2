# upstream examples/crash.py: a crash in user code is a served turn with its
# exit code.
import sys

print("about to crash")
sys.exit(3)
