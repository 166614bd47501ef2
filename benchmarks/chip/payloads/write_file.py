# upstream examples/hello_world_write_file.py, at upstream's own size (14
# bytes): the changed-file scan ships the file back as a content hash.
with open("hello.txt", "w") as f:
    f.write("Hello, World!\n")
print("wrote hello.txt")
