# upstream examples/files.py: write two files, one of them nested, list the
# workspace and read one back.
import os

os.makedirs("out/nested", exist_ok=True)
with open("out/nested/report.txt", "w") as f:
    f.write("generated artifact\n")
with open("top.txt", "w") as f:
    f.write("top-level artifact\n")

for root, _dirs, files in os.walk("."):
    for name in sorted(files):
        print(os.path.join(root, name))
print(open("out/nested/report.txt").read().strip())
