# upstream examples/ls.py: lists the workspace, which a stateless turn must
# find empty whatever the tenant before it left.
import os

entries = sorted(os.listdir("."))
for entry in entries:
    kind = "dir " if os.path.isdir(entry) else "file"
    print(f"{kind} {entry}")
print("entries", len(entries))
