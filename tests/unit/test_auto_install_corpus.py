"""Auto-install resolution hit rate against a realistic corpus (VERDICT r4
#9): ~130 imports an LLM agent's generated snippets actually use — the
reference sandbox's own stack, the classic divergent import→distribution
names, and namespace packages — resolved by executor/deps.py with the
installed-package check disabled (so the MAPPING is what's measured, not
what this machine happens to have installed).

The bar: the reference ships replit upm's full pypi_map.sqlite
(/root/reference/executor/Dockerfile:122-124); deps.py replaces it with a
stdlib filter + curated TSV + identity fallback. This test pins that the
curated table actually covers agent traffic: hit rate >= 95%, and every
miss is listed so a regression names itself.
"""

import importlib.util
from pathlib import Path

import pytest

# Loaded under a name of its own: a bare `import deps` with executor/ left on
# sys.path would put a generically named module (and that directory's
# sitecustomize) in the way of every test collected after this one.
_spec = importlib.util.spec_from_file_location(
    "executor_deps", Path(__file__).resolve().parents[2] / "executor" / "deps.py"
)
deps = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(deps)


# (import statement's module, expected pip distribution(s) — a tuple lists
# acceptable alternatives, None means "must not be pip-installed").
CORPUS: list[tuple[str, object]] = [
    # --- the reference sandbox's own stack (SURVEY §2.16) ---------------
    ("numpy", "numpy"),
    ("pandas", "pandas"),
    ("scipy", "scipy"),
    ("matplotlib", "matplotlib"),
    ("mpl_toolkits.mplot3d", "matplotlib"),
    ("sympy", "sympy"),
    ("cv2", ("opencv-python-headless", "opencv-python")),
    ("moviepy", "moviepy"),
    ("PIL", "pillow"),
    ("xarray", "xarray"),
    ("cowsay", "cowsay"),
    ("pydantic", "pydantic"),
    ("fitz", "pymupdf"),
    ("pdf2image", "pdf2image"),
    ("pikepdf", "pikepdf"),
    ("pypandoc", "pypandoc"),
    ("yt_dlp", "yt-dlp"),
    ("weasyprint", "weasyprint"),
    # --- classic divergent import names ---------------------------------
    ("sklearn", "scikit-learn"),
    ("skimage", "scikit-image"),
    ("bs4", "beautifulsoup4"),
    ("yaml", "pyyaml"),
    ("Crypto", "pycryptodome"),
    ("dateutil", "python-dateutil"),
    ("dotenv", "python-dotenv"),
    ("jwt", ("pyjwt", "PyJWT")),
    ("github", ("pygithub", "PyGithub")),
    ("gitlab", "python-gitlab"),
    ("OpenSSL", ("pyopenssl", "pyOpenSSL")),
    ("magic", "python-magic"),
    ("serial", "pyserial"),
    ("usb", "pyusb"),
    ("attr", "attrs"),
    ("telegram", "python-telegram-bot"),
    ("discord", ("discord.py", "discord-py")),
    ("googleapiclient", "google-api-python-client"),
    ("OpenGL", ("pyopengl", "PyOpenGL")),
    ("Bio", "biopython"),
    ("nacl", "pynacl"),
    ("websocket", "websocket-client"),
    ("websockets", "websockets"),
    ("socks", ("pysocks", "PySocks")),
    ("docx", "python-docx"),
    ("pptx", "python-pptx"),
    ("speech_recognition", ("SpeechRecognition", "speechrecognition")),
    ("tabula", "tabula-py"),
    ("slugify", "python-slugify"),
    ("chess", ("chess", "python-chess")),  # renamed upstream; both valid
    ("barcode", "python-barcode"),
    ("memcache", "python-memcached"),
    ("jose", "python-jose"),
    ("ldap", "python-ldap"),
    ("MySQLdb", "mysqlclient"),
    ("mysql", "mysql-connector-python"),
    ("psycopg2", ("psycopg2-binary", "psycopg2")),
    ("zmq", "pyzmq"),
    ("dns", "dnspython"),
    ("whois", "python-whois"),
    ("nmap", "python-nmap"),
    ("grpc", "grpcio"),
    ("kafka", "kafka-python"),
    ("faiss", ("faiss-cpu", "faiss")),
    ("sentence_transformers", "sentence-transformers"),
    ("flask_cors", "flask-cors"),
    ("flask_sqlalchemy", "flask-sqlalchemy"),
    ("pkg_resources", "setuptools"),
    ("gridfs", "pymongo"),
    ("Levenshtein", ("levenshtein", "python-levenshtein", "Levenshtein")),
    ("fuzzywuzzy", "fuzzywuzzy"),
    ("charset_normalizer", "charset-normalizer"),
    ("email_validator", "email-validator"),
    ("unidecode", ("unidecode", "Unidecode")),
    ("xlsxwriter", ("xlsxwriter", "XlsxWriter")),
    ("odf", "odfpy"),
    ("pyzbar", "pyzbar"),
    ("wx", ("wxpython", "wxPython")),
    ("cairo", "pycairo"),
    ("igraph", ("igraph", "python-igraph")),
    # --- namespace packages (per-subpackage distributions) ---------------
    ("google.cloud.storage", "google-cloud-storage"),
    ("google.cloud.bigquery", "google-cloud-bigquery"),
    ("google.protobuf", "protobuf"),
    ("google.generativeai", "google-generativeai"),
    ("azure.storage.blob", "azure-storage-blob"),
    ("azure.identity", "azure-identity"),
    ("ruamel.yaml", "ruamel.yaml"),
    # --- identity names agents commonly pull -----------------------------
    ("requests", "requests"),
    ("httpx", "httpx"),
    ("aiohttp", "aiohttp"),
    ("urllib3", "urllib3"),
    ("flask", "flask"),
    ("django", "django"),
    ("fastapi", "fastapi"),
    ("uvicorn", "uvicorn"),
    ("starlette", "starlette"),
    ("jinja2", "jinja2"),
    ("sqlalchemy", "sqlalchemy"),
    ("redis", "redis"),
    ("pymongo", "pymongo"),
    ("elasticsearch", "elasticsearch"),
    ("boto3", "boto3"),
    ("openai", "openai"),
    ("anthropic", "anthropic"),
    ("tiktoken", "tiktoken"),
    ("transformers", "transformers"),
    ("datasets", "datasets"),
    ("huggingface_hub", "huggingface-hub"),
    ("torch", "torch"),
    ("torchvision", "torchvision"),
    ("tensorflow", "tensorflow"),
    ("keras", "keras"),
    ("jax", "jax"),
    ("einops", "einops"),
    ("seaborn", "seaborn"),
    ("plotly", "plotly"),
    ("bokeh", "bokeh"),
    ("altair", "altair"),
    ("networkx", "networkx"),
    ("statsmodels", "statsmodels"),
    ("geopandas", "geopandas"),
    ("shapely", "shapely"),
    ("folium", "folium"),
    ("geopy", "geopy"),
    ("pytz", "pytz"),
    ("arrow", "arrow"),
    ("pendulum", "pendulum"),
    ("dateparser", "dateparser"),
    ("humanize", "humanize"),
    ("phonenumbers", "phonenumbers"),
    ("pycountry", "pycountry"),
    ("faker", "faker"),
    ("nltk", "nltk"),
    ("spacy", "spacy"),
    ("gensim", "gensim"),
    ("textblob", "textblob"),
    ("wordcloud", "wordcloud"),
    ("emoji", "emoji"),
    ("psutil", "psutil"),
    ("paramiko", "paramiko"),
    ("pexpect", "pexpect"),
    ("py7zr", "py7zr"),
    ("rarfile", "rarfile"),
    ("pydub", "pydub"),
    ("librosa", "librosa"),
    ("soundfile", "soundfile"),
    ("mido", "mido"),
    ("music21", "music21"),
    ("pygame", "pygame"),
    ("qrcode", "qrcode"),
    ("tqdm", "tqdm"),
    ("rich", "rich"),
    ("click", "click"),
    ("typer", "typer"),
    ("fire", "fire"),
    ("colorama", "colorama"),
    ("tabulate", "tabulate"),
    ("openpyxl", "openpyxl"),
    ("xlrd", "xlrd"),
    ("h5py", "h5py"),
    ("pyarrow", "pyarrow"),
    ("numba", "numba"),
    ("regex", "regex"),
    ("ujson", "ujson"),
    ("orjson", "orjson"),
    ("msgpack", "msgpack"),
    ("lxml", "lxml"),
    ("html5lib", "html5lib"),
    ("markdown", "markdown"),
    ("bleach", "bleach"),
    ("pytesseract", "pytesseract"),
    # --- must NEVER pip-install (stdlib / system-only) --------------------
    ("os", None),
    ("json", None),
    ("asyncio", None),
    ("sqlite3", None),
    ("tkinter", None),
    ("gi", None),
]


def _resolve(module: str, monkeypatch) -> str | None:
    """What deps.py would pip-install for `import <module>`, with the
    installed-check neutralized so the mapping itself is measured."""
    monkeypatch.setattr(deps, "_find_spec_safe", lambda name: None)
    out = deps.missing_packages(f"import {module}\n")
    assert len(out) <= 1
    return out[0] if out else None


def test_corpus_hit_rate(monkeypatch):
    monkeypatch.setattr(deps, "_find_spec_safe", lambda name: None)
    misses = []
    for module, expected in CORPUS:
        got = deps.missing_packages(f"import {module}\n")
        got = got[0] if got else None
        ok_values = (
            expected if isinstance(expected, tuple) else (expected,)
        )
        normalized = {
            (v.lower() if isinstance(v, str) else v) for v in ok_values
        }
        got_n = got.lower() if isinstance(got, str) else got
        if got_n not in normalized:
            misses.append((module, got, expected))
    hit_rate = 1 - len(misses) / len(CORPUS)
    assert hit_rate >= 0.95, (
        f"hit rate {hit_rate:.1%} over {len(CORPUS)} imports; "
        f"misses: {misses}"
    )
    # Record the measured rate where the round artifacts can see it.
    print(f"\nAUTO_INSTALL_HIT_RATE={hit_rate:.3f} corpus={len(CORPUS)} "
          f"misses={len(misses)}")
    if misses:
        print(f"missed: {misses}")


def test_stdlib_never_installs(monkeypatch):
    monkeypatch.setattr(deps, "_find_spec_safe", lambda name: None)
    src = "import os, json, re, sys, math, pathlib, subprocess\n"
    assert deps.missing_packages(src) == []


def test_from_import_namespace(monkeypatch):
    """`from google.cloud import bigquery` must resolve the SUBpackage
    distribution, not a bogus top-level 'google'."""
    monkeypatch.setattr(deps, "_find_spec_safe", lambda name: None)
    out = deps.missing_packages("from google.cloud import bigquery\n")
    assert out == ["google-cloud-bigquery"]
