/* Native wire codec: the Catalyst-serializer object graph in C.
 *
 * Byte-identical to copycat_tpu/io/serializer.py (the pure-Python
 * reference implementation and fallback): zigzag-LEB128 varints,
 * big-endian f64, tagged primitives/containers, registered types as
 * tag 16+id. Three class shapes (serializer.py's registries say which):
 * generic field-list classes (protocol.messages.Message subclasses —
 * the whole session/RPC hot path) are walked entirely in C; so are
 * classes with a FIXED HEAD before their generic fields (the log
 * entries of server/log.py: raw big-endian i64 index, i64 term, f64
 * timestamp, as BufferOutput.write_i64/write_f64 write them, then each
 * field); classes with any other hand-written write_object/read_object
 * round-trip through Python callbacks registered at configure() time.
 *
 * Anything the C path cannot express raises Fallback, and
 * Serializer.write/read re-runs the pure-Python codec — the native
 * path is an accelerator, never a semantic fork.
 *
 * Reference framing: the reference's serializer is the external
 * Catalyst jar running on the JVM's JIT; this is the equivalent
 * native runtime component (SURVEY.md section 2.3 "serialization").
 */
#define PY_SSIZE_T_CLEAN
#include <Python.h>
#include <string.h>

/* wire tags (serializer.py) */
#define T_NULL 0
#define T_TRUE 1
#define T_FALSE 2
#define T_INT 3
#define T_FLOAT 4
#define T_STR 5
#define T_BYTES 6
#define T_LIST 7
#define T_DICT 8
#define T_TUPLE 9
#define T_SET 10
#define T_CLASS 11

/* What this binary can do, checked by io/codec.py before it is loaded
 * (the marker is searched for in the file's bytes) and after (ABI): a
 * binary built from an older source beside newer Python would walk a
 * class shape it does not know as plain fields, i.e. write other bytes.
 * 2: the fixed head (configure's 7th argument). */
#define CODEC_ABI 2
#define CODEC_STR2(x) #x
#define CODEC_STR(x) CODEC_STR2(x)
static const char codec_abi_marker[] =
    "copycat_codec_abi=" CODEC_STR(CODEC_ABI);

/* module state: live dicts owned by serializer.py + callbacks */
static PyObject *g_id_by_type;   /* dict: type -> int */
static PyObject *g_type_by_id;   /* dict: int -> type */
static PyObject *g_fields_by_id; /* dict: int -> tuple[str] | None */
static PyObject *g_optional_by_id; /* dict: int -> int (trailing optional) */
static PyObject *g_head_by_id;   /* dict: int -> ((name, "i64"|"f64"), ...) */
static PyObject *g_encode_body;  /* callable(obj) -> bytes (custom types) */
static PyObject *g_decode_body;  /* callable(cls, bytes, pos) -> (obj, pos) */
static PyObject *g_fallback;     /* exception type */
static PyObject *g_empty_args;   /* cached () for direct tp_new calls */

/* ------------------------------------------------------------------ */
/* writer                                                              */

typedef struct {
    unsigned char *buf;
    Py_ssize_t len, cap;
} Writer;

static int w_reserve(Writer *w, Py_ssize_t extra) {
    if (w->len + extra <= w->cap) return 0;
    Py_ssize_t cap = w->cap ? w->cap : 256;
    while (cap < w->len + extra) cap *= 2;
    unsigned char *nb = PyMem_Realloc(w->buf, cap);
    if (!nb) { PyErr_NoMemory(); return -1; }
    w->buf = nb;
    w->cap = cap;
    return 0;
}

static int w_raw(Writer *w, const void *p, Py_ssize_t n) {
    if (w_reserve(w, n) < 0) return -1;
    memcpy(w->buf + w->len, p, n);
    w->len += n;
    return 0;
}

/* LEB128 of an already-zigzagged value */
static int w_uvarint(Writer *w, unsigned long long zz) {
    if (w_reserve(w, 10) < 0) return -1;
    while (zz >= 0x80) {
        w->buf[w->len++] = (unsigned char)(zz & 0x7F) | 0x80;
        zz >>= 7;
    }
    w->buf[w->len++] = (unsigned char)zz;
    return 0;
}

static int w_varint(Writer *w, long long v) {
    unsigned long long zz =
        ((unsigned long long)v << 1) ^ (unsigned long long)(v >> 63);
    return w_uvarint(w, zz);
}

static int w_i64(Writer *w, long long v) {
    unsigned long long u = (unsigned long long)v;
    unsigned char be[8];
    for (int i = 0; i < 8; i++) be[i] = (unsigned char)(u >> (56 - 8 * i));
    return w_raw(w, be, 8);
}

static int w_f64(Writer *w, double d) {
    union { double d; unsigned long long u; } x;
    x.d = d;
    unsigned char be[8];
    for (int i = 0; i < 8; i++) be[i] = (unsigned char)(x.u >> (56 - 8 * i));
    return w_raw(w, be, 8);
}

/* ------------------------------------------------------------------ */
/* reader                                                              */

typedef struct {
    const unsigned char *data;
    Py_ssize_t len, pos;
    PyObject *source; /* bytes object backing `data` (borrowed) */
} Reader;

static int r_need(Reader *r, Py_ssize_t n) {
    /* `pos + n` could overflow for a crafted length varint — compare
     * against the remaining bytes instead (r->len - r->pos never
     * overflows); reject negative n here too, belt and braces */
    if (n < 0 || n > r->len - r->pos) {
        PyErr_Format(PyExc_EOFError, "buffer underflow: need %zd at %zd/%zd",
                     n, r->pos, r->len);
        return -1;
    }
    return 0;
}

/* returns 0 on success; *out = decoded (un-zigzagged) value. Overflowing
 * 64 zigzag bits raises Fallback (arbitrary-precision ints take the
 * pure-Python path). */
static int r_varint(Reader *r, long long *out) {
    unsigned long long zz = 0;
    int shift = 0;
    for (;;) {
        if (r_need(r, 1) < 0) return -1;
        unsigned char b = r->data[r->pos++];
        unsigned long long chunk = b & 0x7F;
        if (shift > 63 || (shift == 63 && chunk > 1)) {
            PyErr_SetString(g_fallback, "varint exceeds 64 bits");
            return -1;
        }
        zz |= chunk << shift;
        if (!(b & 0x80)) break;
        shift += 7;
    }
    *out = (long long)(zz >> 1) ^ -(long long)(zz & 1);
    return 0;
}

static int r_i64(Reader *r, long long *out) {
    if (r_need(r, 8) < 0) return -1;
    unsigned long long u = 0;
    for (int i = 0; i < 8; i++) u = (u << 8) | r->data[r->pos++];
    *out = (long long)u;
    return 0;
}

static int r_f64(Reader *r, double *out) {
    if (r_need(r, 8) < 0) return -1;
    unsigned long long u = 0;
    for (int i = 0; i < 8; i++) u = (u << 8) | r->data[r->pos++];
    union { double d; unsigned long long u; } x;
    x.u = u;
    *out = x.d;
    return 0;
}

/* ------------------------------------------------------------------ */
/* fixed head: ((attribute name, "i64" | "f64"), ...) before the fields */

/* the table is a live dict Python owns: never trust its shape */
static int bad_head(void) {
    PyErr_SetString(g_fallback, "fixed head the C walker cannot read");
    return 0;
}

/* 'i' / 'f' for a well-formed (name, kind) pair, else 0 with Fallback */
static int head_item(PyObject *item, PyObject **name) {
    if (PyTuple_Check(item) && PyTuple_GET_SIZE(item) == 2) {
        PyObject *kind = PyTuple_GET_ITEM(item, 1);
        *name = PyTuple_GET_ITEM(item, 0);
        if (PyUnicode_Check(*name) && PyUnicode_Check(kind)) {
            if (PyUnicode_CompareWithASCIIString(kind, "i64") == 0)
                return 'i';
            if (PyUnicode_CompareWithASCIIString(kind, "f64") == 0)
                return 'f';
        }
    }
    return bad_head();
}

/* What struct's ">q" / ">d" would refuse or convert (an int beyond 64
 * bits, a timestamp that is not a float) raises Fallback: the Python
 * walk then answers for it, with its own error where it has one. */
static int enc_head(PyObject *obj, PyObject *head, Writer *w) {
    if (!PyTuple_Check(head)) { bad_head(); return -1; }
    Py_ssize_t n = PyTuple_GET_SIZE(head);
    for (Py_ssize_t i = 0; i < n; i++) {
        PyObject *name;
        int kind = head_item(PyTuple_GET_ITEM(head, i), &name);
        if (!kind) return -1;
        PyObject *val = PyObject_GetAttr(obj, name);
        if (!val) return -1;
        int rc = -1;
        if (kind == 'i') {
            int overflow = 1; /* not an int at all: Python's to answer */
            long long v = 0;
            if (PyLong_Check(val))
                v = PyLong_AsLongLongAndOverflow(val, &overflow);
            if (overflow)
                PyErr_SetString(g_fallback, "head int beyond a raw i64");
            else
                rc = w_i64(w, v);
        } else if (PyFloat_Check(val)) {
            rc = w_f64(w, PyFloat_AS_DOUBLE(val));
        } else {
            PyErr_SetString(g_fallback, "head float is not a float");
        }
        Py_DECREF(val);
        if (rc < 0) return -1;
    }
    return 0;
}

static int dec_head(Reader *r, PyObject *obj, PyObject *head) {
    if (!PyTuple_Check(head)) { bad_head(); return -1; }
    Py_ssize_t n = PyTuple_GET_SIZE(head);
    for (Py_ssize_t i = 0; i < n; i++) {
        PyObject *name, *val;
        int kind = head_item(PyTuple_GET_ITEM(head, i), &name);
        if (!kind) return -1;
        if (kind == 'i') {
            long long v;
            if (r_i64(r, &v) < 0) return -1;
            val = PyLong_FromLongLong(v);
        } else {
            double d;
            if (r_f64(r, &d) < 0) return -1;
            val = PyFloat_FromDouble(d);
        }
        if (!val) return -1;
        int rc = PyObject_SetAttr(obj, name, val);
        Py_DECREF(val);
        if (rc < 0) return -1;
    }
    return 0;
}

/* the class's head, NULL without one (or with an error set) */
static PyObject *head_of(PyObject *idobj) {
    return g_head_by_id ? PyDict_GetItemWithError(g_head_by_id, idobj) : NULL;
}

/* ------------------------------------------------------------------ */
/* encode                                                              */

static int enc(PyObject *obj, Writer *w, int depth);

/* matches Python's recursion limit semantics: deeper graphs fall
 * back to the pure-Python codec, which raises RecursionError
 * cleanly instead of overflowing the C stack (fuzz finding) */
#define MAX_DEPTH 1000

static int enc_seq_items(PyObject *fast, Writer *w, int depth) {
    Py_ssize_t n = PySequence_Fast_GET_SIZE(fast);
    for (Py_ssize_t i = 0; i < n; i++) {
        if (enc(PySequence_Fast_GET_ITEM(fast, i), w, depth) < 0) return -1;
    }
    return 0;
}

static int enc_registered(PyObject *obj, Writer *w, int depth) {
    PyObject *type = (PyObject *)Py_TYPE(obj);
    PyObject *idobj = PyDict_GetItemWithError(g_id_by_type, type);
    if (!idobj) {
        if (!PyErr_Occurred())
            PyErr_Format(g_fallback, "unregistered type %s",
                         Py_TYPE(obj)->tp_name);
        return -1;
    }
    long long tid = PyLong_AsLongLong(idobj);
    if (tid < 0 && PyErr_Occurred()) return -1;
    if (w_varint(w, 16 + tid) < 0) return -1;
    PyObject *fields = PyDict_GetItemWithError(g_fields_by_id, idobj);
    if (!fields) {
        if (PyErr_Occurred()) return -1;
        PyErr_Format(g_fallback, "no codec meta for id %lld", tid);
        return -1;
    }
    if (fields == Py_None) { /* custom write_object via Python */
        PyObject *body = PyObject_CallOneArg(g_encode_body, obj);
        if (!body) return -1;
        char *p;
        Py_ssize_t n;
        if (PyBytes_AsStringAndSize(body, &p, &n) < 0) {
            Py_DECREF(body);
            return -1;
        }
        int rc = w_raw(w, p, n);
        Py_DECREF(body);
        return rc;
    }
    PyObject *head = head_of(idobj);
    if (!head && PyErr_Occurred()) return -1;
    if (head && enc_head(obj, head, w) < 0) return -1;
    Py_ssize_t nf = PyTuple_GET_SIZE(fields);
    /* wire-optional trailing fields (Message._optional): a trailing
     * None run is omitted entirely, matching the Python reference walk
     * — the untraced RPC frame stays byte-identical to the schema
     * before the field existed. */
    PyObject *optobj = g_optional_by_id
        ? PyDict_GetItemWithError(g_optional_by_id, idobj) : NULL;
    if (!optobj && PyErr_Occurred()) return -1;
    long long nopt = 0;
    if (optobj) {
        nopt = PyLong_AsLongLong(optobj);
        if (nopt < 0 && PyErr_Occurred()) return -1;
    }
    while (nopt > 0 && nf > 0) {
        PyObject *tail = PyObject_GetAttr(obj, PyTuple_GET_ITEM(fields,
                                                                nf - 1));
        if (!tail) return -1;
        int is_none = (tail == Py_None);
        Py_DECREF(tail);
        if (!is_none) break;
        nf--;
        nopt--;
    }
    for (Py_ssize_t i = 0; i < nf; i++) {
        PyObject *val = PyObject_GetAttr(obj, PyTuple_GET_ITEM(fields, i));
        if (!val) return -1;
        int rc = enc(val, w, depth);
        Py_DECREF(val);
        if (rc < 0) return -1;
    }
    return 0;
}

static int enc(PyObject *obj, Writer *w, int depth) {
    if (++depth > MAX_DEPTH) {
        PyErr_SetString(g_fallback, "graph too deep for the C walker");
        return -1;
    }
    if (obj == Py_None) return w_varint(w, T_NULL);
    if (obj == Py_True) return w_varint(w, T_TRUE);
    if (obj == Py_False) return w_varint(w, T_FALSE);
    if (PyLong_Check(obj)) {
        int overflow = 0;
        long long v = PyLong_AsLongLongAndOverflow(obj, &overflow);
        if (overflow) {
            PyErr_SetString(g_fallback, "int exceeds 64 bits");
            return -1;
        }
        if (v == -1 && PyErr_Occurred()) return -1;
        if (w_varint(w, T_INT) < 0) return -1;
        return w_varint(w, v);
    }
    if (PyFloat_Check(obj)) {
        if (w_varint(w, T_FLOAT) < 0) return -1;
        return w_f64(w, PyFloat_AS_DOUBLE(obj));
    }
    if (PyUnicode_Check(obj)) {
        Py_ssize_t n;
        const char *s = PyUnicode_AsUTF8AndSize(obj, &n);
        if (!s) return -1;
        if (w_varint(w, T_STR) < 0 || w_varint(w, n) < 0) return -1;
        return w_raw(w, s, n);
    }
    if (PyBytes_Check(obj) || PyByteArray_Check(obj)) {
        char *p;
        Py_ssize_t n;
        if (PyBytes_Check(obj)) {
            if (PyBytes_AsStringAndSize(obj, &p, &n) < 0) return -1;
        } else {
            p = PyByteArray_AS_STRING(obj);
            n = PyByteArray_GET_SIZE(obj);
        }
        if (w_varint(w, T_BYTES) < 0 || w_varint(w, n) < 0) return -1;
        return w_raw(w, p, n);
    }
    if (PyList_Check(obj)) {
        if (w_varint(w, T_LIST) < 0 ||
            w_varint(w, PyList_GET_SIZE(obj)) < 0)
            return -1;
        return enc_seq_items(obj, w, depth);
    }
    if (PyTuple_Check(obj)) {
        if (w_varint(w, T_TUPLE) < 0 ||
            w_varint(w, PyTuple_GET_SIZE(obj)) < 0)
            return -1;
        return enc_seq_items(obj, w, depth);
    }
    if (PyAnySet_Check(obj)) {
        /* Python sorts each item's FULL encoding for determinism */
        Py_ssize_t n = PySet_GET_SIZE(obj);
        if (w_varint(w, T_SET) < 0 || w_varint(w, n) < 0) return -1;
        PyObject *parts = PyList_New(0);
        if (!parts) return -1;
        PyObject *it = PyObject_GetIter(obj), *item;
        if (!it) { Py_DECREF(parts); return -1; }
        while ((item = PyIter_Next(it)) != NULL) {
            Writer iw = {NULL, 0, 0};
            if (enc(item, &iw, depth) < 0) {
                Py_DECREF(item); Py_DECREF(it); Py_DECREF(parts);
                PyMem_Free(iw.buf);
                return -1;
            }
            Py_DECREF(item);
            PyObject *bs = PyBytes_FromStringAndSize((char *)iw.buf, iw.len);
            PyMem_Free(iw.buf);
            if (!bs || PyList_Append(parts, bs) < 0) {
                Py_XDECREF(bs); Py_DECREF(it); Py_DECREF(parts);
                return -1;
            }
            Py_DECREF(bs);
        }
        Py_DECREF(it);
        if (PyErr_Occurred()) { Py_DECREF(parts); return -1; }
        if (PyList_Sort(parts) < 0) { Py_DECREF(parts); return -1; }
        for (Py_ssize_t i = 0; i < PyList_GET_SIZE(parts); i++) {
            PyObject *bs = PyList_GET_ITEM(parts, i);
            if (w_raw(w, PyBytes_AS_STRING(bs), PyBytes_GET_SIZE(bs)) < 0) {
                Py_DECREF(parts);
                return -1;
            }
        }
        Py_DECREF(parts);
        return 0;
    }
    if (PyDict_Check(obj)) {
        if (w_varint(w, T_DICT) < 0 ||
            w_varint(w, PyDict_GET_SIZE(obj)) < 0)
            return -1;
        Py_ssize_t pos = 0;
        PyObject *k, *v;
        while (PyDict_Next(obj, &pos, &k, &v)) {
            if (enc(k, w, depth) < 0 || enc(v, w, depth) < 0) return -1;
        }
        return 0;
    }
    if (PyType_Check(obj)) {
        PyObject *idobj = PyDict_GetItemWithError(g_id_by_type, obj);
        if (!idobj) {
            if (!PyErr_Occurred())
                PyErr_Format(g_fallback, "unregistered class %s",
                             ((PyTypeObject *)obj)->tp_name);
            return -1;
        }
        long long tid = PyLong_AsLongLong(idobj);
        if (tid < 0 && PyErr_Occurred()) return -1;
        if (w_varint(w, T_CLASS) < 0) return -1;
        return w_varint(w, tid);
    }
    return enc_registered(obj, w, depth);
}

/* ------------------------------------------------------------------ */
/* decode                                                              */

static PyObject *dec(Reader *r, int depth);

static PyObject *dec_registered(Reader *r, long long tid, int depth) {
    PyObject *idobj = PyLong_FromLongLong(tid);
    if (!idobj) return NULL;
    PyObject *cls = PyDict_GetItemWithError(g_type_by_id, idobj);
    if (!cls) {
        if (!PyErr_Occurred())
            PyErr_Format(g_fallback, "unknown serialization id %lld", tid);
        Py_DECREF(idobj);
        return NULL;
    }
    PyObject *fields = PyDict_GetItemWithError(g_fields_by_id, idobj);
    if (!fields && PyErr_Occurred()) { Py_DECREF(idobj); return NULL; }
    PyObject *optobj = (fields && g_optional_by_id)
        ? PyDict_GetItemWithError(g_optional_by_id, idobj) : NULL;
    if (!optobj && PyErr_Occurred()) { Py_DECREF(idobj); return NULL; }
    PyObject *head = head_of(idobj);
    Py_DECREF(idobj);
    if (!head && PyErr_Occurred()) return NULL;
    if (!fields) {
        PyErr_Format(g_fallback, "no codec meta for id %lld", tid);
        return NULL;
    }
    if (fields == Py_None) { /* custom read_object via Python */
        PyObject *res = PyObject_CallFunction(
            g_decode_body, "OOn", cls, r->source, r->pos);
        if (!res) return NULL;
        PyObject *obj = PyTuple_GetItem(res, 0);
        PyObject *np = PyTuple_GetItem(res, 1);
        if (!obj || !np) { Py_DECREF(res); return NULL; }
        long long newpos = PyLong_AsLongLong(np);
        if (newpos < 0 && PyErr_Occurred()) { Py_DECREF(res); return NULL; }
        r->pos = (Py_ssize_t)newpos;
        Py_INCREF(obj);
        Py_DECREF(res);
        return obj;
    }
    /* Allocate without running __init__ (the generic field-list read
     * path, like serializer.py read_object). tp_new with empty args is
     * exactly what cls.__new__(cls) resolves to for these plain classes
     * — calling the slot directly skips the per-object attribute lookup
     * and bound-staticmethod allocation (measured on 1k-op batch
     * decodes). Classes overriding __new__ still go through their slot. */
    PyObject *obj;
    newfunc tp_new = ((PyTypeObject *)cls)->tp_new;
    if (tp_new) {
        obj = tp_new((PyTypeObject *)cls, g_empty_args, NULL);
    } else {
        PyObject *newf = PyObject_GetAttrString(cls, "__new__");
        if (!newf) return NULL;
        obj = PyObject_CallOneArg(newf, cls);
        Py_DECREF(newf);
    }
    if (!obj) return NULL;
    if (head && dec_head(r, obj, head) < 0) { Py_DECREF(obj); return NULL; }
    Py_ssize_t nf = PyTuple_GET_SIZE(fields);
    long long nopt = 0;
    if (optobj) {
        nopt = PyLong_AsLongLong(optobj);
        if (nopt < 0 && PyErr_Occurred()) { Py_DECREF(obj); return NULL; }
    }
    Py_ssize_t required = nf - (Py_ssize_t)nopt;
    for (Py_ssize_t i = 0; i < nf; i++) {
        PyObject *val;
        if (i >= required && r->pos >= r->len) {
            /* omitted wire-optional tail: the message ends its buffer
             * (frames carry exactly one message), fill with None —
             * mirrors Message.read_object in the Python reference */
            val = Py_None;
            Py_INCREF(val);
        } else {
            val = dec(r, depth);
            if (!val) { Py_DECREF(obj); return NULL; }
        }
        int rc = PyObject_SetAttr(obj, PyTuple_GET_ITEM(fields, i), val);
        Py_DECREF(val);
        if (rc < 0) { Py_DECREF(obj); return NULL; }
    }
    return obj;
}

static PyObject *dec(Reader *r, int depth) {
    if (++depth > MAX_DEPTH) {
        PyErr_SetString(g_fallback, "wire graph too deep for the C walker");
        return NULL;
    }
    long long tag;
    if (r_varint(r, &tag) < 0) return NULL;
    switch (tag) {
    case T_NULL: Py_RETURN_NONE;
    case T_TRUE: Py_RETURN_TRUE;
    case T_FALSE: Py_RETURN_FALSE;
    case T_INT: {
        long long v;
        if (r_varint(r, &v) < 0) return NULL;
        return PyLong_FromLongLong(v);
    }
    case T_FLOAT: {
        double d;
        if (r_f64(r, &d) < 0) return NULL;
        return PyFloat_FromDouble(d);
    }
    case T_STR: {
        long long n;
        if (r_varint(r, &n) < 0) return NULL;
        if (n < 0 || r_need(r, (Py_ssize_t)n) < 0) return NULL;
        PyObject *s = PyUnicode_DecodeUTF8(
            (const char *)r->data + r->pos, (Py_ssize_t)n, NULL);
        if (s) r->pos += (Py_ssize_t)n;
        return s;
    }
    case T_BYTES: {
        long long n;
        if (r_varint(r, &n) < 0) return NULL;
        if (n < 0 || r_need(r, (Py_ssize_t)n) < 0) return NULL;
        PyObject *b = PyBytes_FromStringAndSize(
            (const char *)r->data + r->pos, (Py_ssize_t)n);
        if (b) r->pos += (Py_ssize_t)n;
        return b;
    }
    case T_LIST: {
        long long n;
        if (r_varint(r, &n) < 0 || n < 0) return NULL;
        PyObject *lst = PyList_New((Py_ssize_t)n);
        if (!lst) return NULL;
        for (Py_ssize_t i = 0; i < (Py_ssize_t)n; i++) {
            PyObject *item = dec(r, depth);
            if (!item) { Py_DECREF(lst); return NULL; }
            PyList_SET_ITEM(lst, i, item);
        }
        return lst;
    }
    case T_TUPLE: {
        long long n;
        if (r_varint(r, &n) < 0 || n < 0) return NULL;
        PyObject *tup = PyTuple_New((Py_ssize_t)n);
        if (!tup) return NULL;
        for (Py_ssize_t i = 0; i < (Py_ssize_t)n; i++) {
            PyObject *item = dec(r, depth);
            if (!item) { Py_DECREF(tup); return NULL; }
            PyTuple_SET_ITEM(tup, i, item);
        }
        return tup;
    }
    case T_SET: {
        long long n;
        if (r_varint(r, &n) < 0 || n < 0) return NULL;
        PyObject *set = PySet_New(NULL);
        if (!set) return NULL;
        for (Py_ssize_t i = 0; i < (Py_ssize_t)n; i++) {
            PyObject *item = dec(r, depth);
            if (!item || PySet_Add(set, item) < 0) {
                Py_XDECREF(item); Py_DECREF(set);
                return NULL;
            }
            Py_DECREF(item);
        }
        return set;
    }
    case T_DICT: {
        long long n;
        if (r_varint(r, &n) < 0 || n < 0) return NULL;
        PyObject *d = PyDict_New();
        if (!d) return NULL;
        for (Py_ssize_t i = 0; i < (Py_ssize_t)n; i++) {
            PyObject *k = dec(r, depth); /* key first, like the dict comp */
            if (!k) { Py_DECREF(d); return NULL; }
            PyObject *v = dec(r, depth);
            if (!v || PyDict_SetItem(d, k, v) < 0) {
                Py_DECREF(k); Py_XDECREF(v); Py_DECREF(d);
                return NULL;
            }
            Py_DECREF(k);
            Py_DECREF(v);
        }
        return d;
    }
    case T_CLASS: {
        long long tid;
        if (r_varint(r, &tid) < 0) return NULL;
        PyObject *idobj = PyLong_FromLongLong(tid);
        if (!idobj) return NULL;
        PyObject *cls = PyDict_GetItemWithError(g_type_by_id, idobj);
        Py_DECREF(idobj);
        if (!cls) {
            if (!PyErr_Occurred())
                PyErr_Format(g_fallback, "unknown class id %lld", tid);
            return NULL;
        }
        Py_INCREF(cls);
        return cls;
    }
    default:
        if (tag < 16) {
            PyErr_Format(g_fallback, "unknown wire tag %lld", tag);
            return NULL;
        }
        return dec_registered(r, tag - 16, depth);
    }
}

/* ------------------------------------------------------------------ */
/* module functions                                                    */

static PyObject *codec_encode(PyObject *self, PyObject *obj) {
    (void)self;
    Writer w = {NULL, 0, 0};
    if (enc(obj, &w, 0) < 0) {
        PyMem_Free(w.buf);
        return NULL;
    }
    PyObject *out = PyBytes_FromStringAndSize((char *)w.buf, w.len);
    PyMem_Free(w.buf);
    return out;
}

static PyObject *codec_decode(PyObject *self, PyObject *data) {
    (void)self;
    if (!PyBytes_Check(data)) {
        PyErr_SetString(PyExc_TypeError, "decode() needs bytes");
        return NULL;
    }
    Reader r = {(const unsigned char *)PyBytes_AS_STRING(data),
                PyBytes_GET_SIZE(data), 0, data};
    PyObject *obj = dec(&r, 0);
    if (obj && r.pos != r.len) {
        /* trailing bytes mean a framing mismatch — surface it */
        Py_DECREF(obj);
        PyErr_Format(g_fallback, "decode left %zd trailing bytes",
                     r.len - r.pos);
        return NULL;
    }
    return obj;
}

/* ------------------------------------------------------------------ */
/* frame-burst walk: [u32 len][u8 kind][u64 corr][payload]...           */
/* The shared TCP wire framing (io/tcp.py _HEADER = ">IBQ") walked in   */
/* one call per read burst: the transports hand whole read buffers to   */
/* decode_frames and whole response bursts to encode_frames, so the    */
/* session frame walk — batch envelope in, per-op decode, response     */
/* re-encode — stays in C for the full request/response cycle.         */

#define FRAME_HEADER 13

static PyObject *codec_decode_frames(PyObject *self, PyObject *data) {
    (void)self;
    /* buffer protocol, not PyBytes: the TCP read loop accumulates into
       a bytearray (amortized O(n) appends); every decoded object copies
       out of the buffer, so nothing references it after the call */
    Py_buffer view;
    if (PyObject_GetBuffer(data, &view, PyBUF_SIMPLE) != 0) {
        return NULL;
    }
    const unsigned char *buf = (const unsigned char *)view.buf;
    Py_ssize_t total = view.len;
    PyObject *out = PyList_New(0);
    if (!out) { PyBuffer_Release(&view); return NULL; }
    Py_ssize_t pos = 0;
    while (pos + FRAME_HEADER <= total) {
        unsigned long long length = 0, corr = 0;
        for (int i = 0; i < 4; i++) length = (length << 8) | buf[pos + i];
        unsigned char kind = buf[pos + 4];
        for (int i = 0; i < 8; i++) corr = (corr << 8) | buf[pos + 5 + i];
        if (pos + FRAME_HEADER + (Py_ssize_t)length > total) break;
        Reader r = {buf, pos + FRAME_HEADER + (Py_ssize_t)length,
                    pos + FRAME_HEADER, data};
        PyObject *obj = dec(&r, 0);
        if (!obj) { /* incl. Fallback: the caller re-walks this burst
                       frame-by-frame in Python */
            Py_DECREF(out); PyBuffer_Release(&view); return NULL;
        }
        if (r.pos != r.len) {
            Py_DECREF(obj); Py_DECREF(out);
            PyErr_Format(g_fallback, "frame decode left %zd trailing bytes",
                         r.len - r.pos);
            PyBuffer_Release(&view);
            return NULL;
        }
        PyObject *rec = Py_BuildValue("(iKN)", (int)kind, corr, obj);
        if (!rec || PyList_Append(out, rec) < 0) {
            Py_XDECREF(rec); Py_DECREF(out);
            PyBuffer_Release(&view);
            return NULL;
        }
        Py_DECREF(rec);
        pos += FRAME_HEADER + (Py_ssize_t)length;
    }
    PyBuffer_Release(&view);
    return Py_BuildValue("(Nn)", out, pos);
}

static PyObject *codec_encode_frames(PyObject *self, PyObject *frames) {
    (void)self;
    PyObject *fast = PySequence_Fast(frames,
                                     "encode_frames() needs a sequence");
    if (!fast) return NULL;
    Writer w = {NULL, 0, 0};
    Py_ssize_t n = PySequence_Fast_GET_SIZE(fast);
    for (Py_ssize_t i = 0; i < n; i++) {
        PyObject *item = PySequence_Fast_GET_ITEM(fast, i);
        int kind;
        unsigned long long corr;
        PyObject *obj;
        if (!PyArg_ParseTuple(item, "iKO", &kind, &corr, &obj)) {
            Py_DECREF(fast); PyMem_Free(w.buf);
            return NULL;
        }
        Py_ssize_t hdr = w.len;
        if (w_reserve(&w, FRAME_HEADER) < 0) {
            Py_DECREF(fast); PyMem_Free(w.buf);
            return NULL;
        }
        w.len += FRAME_HEADER;
        if (enc(obj, &w, 0) < 0) {
            Py_DECREF(fast); PyMem_Free(w.buf);
            return NULL;
        }
        unsigned long long length = (unsigned long long)(w.len - hdr
                                                         - FRAME_HEADER);
        for (int b = 0; b < 4; b++)
            w.buf[hdr + b] = (unsigned char)(length >> (24 - 8 * b));
        w.buf[hdr + 4] = (unsigned char)kind;
        for (int b = 0; b < 8; b++)
            w.buf[hdr + 5 + b] = (unsigned char)(corr >> (56 - 8 * b));
    }
    Py_DECREF(fast);
    PyObject *out = PyBytes_FromStringAndSize((char *)w.buf, w.len);
    PyMem_Free(w.buf);
    return out;
}

static PyObject *codec_configure(PyObject *self, PyObject *args) {
    (void)self;
    PyObject *ibt, *tbi, *fbi, *eb, *db, *obi = NULL, *hbi = NULL;
    if (!PyArg_ParseTuple(args, "OOOOO|OO", &ibt, &tbi, &fbi, &eb, &db,
                          &obi, &hbi))
        return NULL;
    Py_XDECREF(g_id_by_type); Py_INCREF(ibt); g_id_by_type = ibt;
    Py_XDECREF(g_type_by_id); Py_INCREF(tbi); g_type_by_id = tbi;
    Py_XDECREF(g_fields_by_id); Py_INCREF(fbi); g_fields_by_id = fbi;
    Py_XDECREF(g_encode_body); Py_INCREF(eb); g_encode_body = eb;
    Py_XDECREF(g_decode_body); Py_INCREF(db); g_decode_body = db;
    Py_XDECREF(g_optional_by_id); Py_XINCREF(obi); g_optional_by_id = obi;
    Py_XDECREF(g_head_by_id); Py_XINCREF(hbi); g_head_by_id = hbi;
    Py_RETURN_NONE;
}

static PyMethodDef codec_methods[] = {
    {"configure", codec_configure, METH_VARARGS,
     "configure(id_by_type, type_by_id, fields_by_id, encode_body, "
     "decode_body[, optional_by_id[, head_by_id]]) — bind the live "
     "registries + fallback hooks."},
    {"encode", codec_encode, METH_O, "encode(obj) -> bytes"},
    {"decode", codec_decode, METH_O, "decode(bytes) -> obj"},
    {"decode_frames", codec_decode_frames, METH_O,
     "decode_frames(bytes) -> ([(kind, corr, obj), ...], consumed) — walk "
     "complete [u32 len][u8 kind][u64 corr][payload] frames in one call."},
    {"encode_frames", codec_encode_frames, METH_O,
     "encode_frames([(kind, corr, obj), ...]) -> bytes — one framed "
     "buffer for a whole response burst."},
    {NULL, NULL, 0, NULL}};

static struct PyModuleDef codec_module = {
    PyModuleDef_HEAD_INIT, "copycat_codec",
    "Native Catalyst-wire codec (see io/serializer.py for the format).",
    -1, codec_methods, NULL, NULL, NULL, NULL};

PyMODINIT_FUNC PyInit_copycat_codec(void) {
    PyObject *m = PyModule_Create(&codec_module);
    if (!m) return NULL;
    g_empty_args = PyTuple_New(0);
    if (!g_empty_args) {
        Py_DECREF(m);
        return NULL;
    }
    g_fallback = PyErr_NewException("copycat_codec.Fallback", NULL, NULL);
    if (!g_fallback || PyModule_AddObject(m, "Fallback", g_fallback) < 0) {
        Py_XDECREF(g_fallback);
        Py_DECREF(m);
        return NULL;
    }
    Py_INCREF(g_fallback); /* module owns one ref; we keep the global */
    if (PyModule_AddIntConstant(m, "ABI", CODEC_ABI) < 0 ||
        PyModule_AddStringConstant(m, "ABI_MARKER", codec_abi_marker) < 0) {
        Py_DECREF(m);
        return NULL;
    }
    return m;
}
