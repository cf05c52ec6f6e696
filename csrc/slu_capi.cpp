// C ABI for the TPU-native sparse direct solver — the binding layer
// for non-Python hosts (C, C++, Fortran via ISO_C_BINDING).
//
// Reference analog: the Fortran-90 interface (FORTRAN/
// superlu_c2f_dwrap.c:142 `f_pdgssvx`, opaque `fptr` handles;
// FORTRAN/superlu_mod.f90:11).  The reference wraps C structs behind
// integer handles for F90; this build wraps the Python driver behind a
// C ABI by EMBEDDING CPython — the C caller reaches exactly the same
// gssvx pipeline (plan, factor, solve, refine, all reuse rungs) that
// Python callers use, marshaled zero-copy through pointer addresses
// (superlu_dist_tpu/capi_bridge.py).
//
// Threading contract: calls are serialized by the GIL; each entry
// point takes it (PyGILState_Ensure) and releases it on exit.  The
// library may live alongside an existing interpreter (it then skips
// Py_Initialize and only adds the repo to sys.path).
//
// Fortran mapping (ISO_C_BINDING): integer(c_int64_t) scalars/arrays,
// real(c_double) arrays, character(kind=c_char) strings; dense blocks
// are COLUMN-major (n, nrhs) — the natural Fortran layout.
//
// Build: `make libslu_tpu_c.so` in csrc/ (links libpython; see
// Makefile).  Demo + test: csrc/capi_demo.c, tests/test_capi.py.

#include <Python.h>

#include <cstdint>
#include <cstring>
#include <string>

namespace {

std::string g_err;
PyThreadState* g_tstate = nullptr;
bool g_we_initialized = false;

// Fetch (and thereby CLEAR) the pending Python exception into g_err —
// callers must not leave the error indicator set across API calls.
void set_err_from_python() {
  PyObject *type, *value, *tb;
  PyErr_Fetch(&type, &value, &tb);
  PyErr_NormalizeException(&type, &value, &tb);
  g_err = "python error";
  if (value) {
    PyObject* s = PyObject_Str(value);
    if (s) {
      const char* c = PyUnicode_AsUTF8(s);
      if (c) g_err = c;
      Py_DECREF(s);
    }
  }
  Py_XDECREF(type);
  Py_XDECREF(value);
  Py_XDECREF(tb);
}

// Call superlu_dist_tpu.capi_bridge.<fn>(*args); returns the int
// result, or -1 with g_err set.
long long call_bridge(const char* fn, PyObject* args) {
  PyGILState_STATE st = PyGILState_Ensure();
  long long rc = -1;
  PyObject* mod = PyImport_ImportModule("superlu_dist_tpu.capi_bridge");
  if (!mod) {
    set_err_from_python();
  } else {
    PyObject* f = PyObject_GetAttrString(mod, fn);
    if (!f) {
      set_err_from_python();
    } else {
      PyObject* out = PyObject_CallObject(f, args);
      if (!out) {
        set_err_from_python();
      } else {
        rc = PyLong_AsLongLong(out);
        if (rc == -1 && PyErr_Occurred()) set_err_from_python();
        Py_DECREF(out);
      }
      Py_DECREF(f);
    }
    Py_DECREF(mod);
  }
  Py_XDECREF(args);
  PyGILState_Release(st);
  return rc;
}

}  // namespace

extern "C" {

// Initialize the embedded interpreter.  repo_path: directory holding
// the superlu_dist_tpu package (appended to sys.path; pass NULL if it
// is already importable).  force_cpu != 0 pins JAX_PLATFORMS=cpu
// BEFORE jax can initialize — the safe default on hosts without an
// accelerator.  Returns 0 on success; idempotent.
int64_t slu_tpu_init(const char* repo_path, int64_t force_cpu) {
  if (force_cpu) setenv("JAX_PLATFORMS", "cpu", 1);
  if (!Py_IsInitialized()) {
    Py_InitializeEx(0);
    g_we_initialized = true;
  }
  // holding thread state: we were handed the GIL by Py_Initialize (or
  // must take it if embedding into an existing interpreter)
  PyGILState_STATE st = PyGILState_Ensure();
  int64_t rc = 0;
  if (repo_path && repo_path[0]) {
    PyObject* sys_path = PySys_GetObject("path");  // borrowed
    PyObject* p = PyUnicode_FromString(repo_path);
    if (!sys_path || !p || PyList_Insert(sys_path, 0, p) != 0) {
      set_err_from_python();
      rc = -1;
    }
    Py_XDECREF(p);
  }
  PyGILState_Release(st);
  if (g_we_initialized && !g_tstate) {
    // release the GIL acquired by Py_Initialize so later calls (from
    // any thread) can PyGILState_Ensure it
    g_tstate = PyEval_SaveThread();
  }
  return rc;
}

// One-call expert driver (f_pdgssvx analog): CSR (int64 indptr/
// indices, double values), column-major b/x (n, nrhs).  options is a
// "key=value,key=value" string (colperm=, rowperm=, refine=, trans=,
// factor_dtype=, equil=, backend=); NULL/"" for defaults.  berr_out
// may be NULL.  Returns 0 on success.
int64_t slu_tpu_solve(int64_t n, int64_t nnz, const int64_t* indptr,
                      const int64_t* indices, const double* values,
                      int64_t nrhs, const double* b, double* x,
                      double* berr_out, const char* options) {
  PyGILState_STATE st = PyGILState_Ensure();
  PyObject* args = Py_BuildValue(
      "(LLLLLLLLLs)", (long long)n, (long long)nnz,
      (long long)(intptr_t)indptr, (long long)(intptr_t)indices,
      (long long)(intptr_t)values, (long long)nrhs,
      (long long)(intptr_t)b, (long long)(intptr_t)x,
      (long long)(intptr_t)berr_out, options ? options : "");
  if (!args) set_err_from_python();  // also clears the indicator
  PyGILState_Release(st);
  if (!args) return -1;
  return call_bridge("solve", args);
}

// Opaque-handle factorization (the LUstruct/SOLVEstruct persistence
// pattern; enables the Fact reuse ladder from C).  Returns a positive
// handle, or -1.
int64_t slu_tpu_factorize(int64_t n, int64_t nnz, const int64_t* indptr,
                          const int64_t* indices, const double* values,
                          const char* options) {
  PyGILState_STATE st = PyGILState_Ensure();
  PyObject* args = Py_BuildValue(
      "(LLLLLs)", (long long)n, (long long)nnz,
      (long long)(intptr_t)indptr, (long long)(intptr_t)indices,
      (long long)(intptr_t)values, options ? options : "");
  if (!args) set_err_from_python();
  PyGILState_Release(st);
  if (!args) return -1;
  return call_bridge("factorize", args);
}

// Solve against a persistent factorization; trans != 0 solves Aᵀx=b.
int64_t slu_tpu_solve_factored(int64_t handle, int64_t nrhs,
                               const double* b, double* x,
                               int64_t trans) {
  PyGILState_STATE st = PyGILState_Ensure();
  PyObject* args = Py_BuildValue(
      "(LLLLL)", (long long)handle, (long long)nrhs,
      (long long)(intptr_t)b, (long long)(intptr_t)x,
      (long long)trans);
  if (!args) set_err_from_python();
  PyGILState_Release(st);
  if (!args) return -1;
  return call_bridge("solve_factored", args);
}

int64_t slu_tpu_free(int64_t handle) {
  PyGILState_STATE st = PyGILState_Ensure();
  PyObject* args = Py_BuildValue("(L)", (long long)handle);
  if (!args) set_err_from_python();
  PyGILState_Release(st);
  if (!args) return -1;
  return call_bridge("free", args);
}

// Last error message (valid until the next failing call).
const char* slu_tpu_last_error(void) { return g_err.c_str(); }

}  // extern "C"
