# upstream examples/hello_world_read_file.py: reads the file that
# hello_world_write_file.py produced, carried in through the files map.
print(open("hello.txt").read().strip())
